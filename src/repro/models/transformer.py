"""Decoder-only transformer LM (Llama-3-like, the paper's own family):
RMSNorm pre-norm blocks, GQA attention with RoPE, SwiGLU or MoE FFN,
optional sliding-window attention and multimodal prefix embeddings.

Layers are *stacked* (leading 'layers' dim) and applied with lax.scan —
essential to keep XLA compile time sane at 512 devices x 40+ layers.
Optional leading dense layers (DeepSeek-MoE's first-layer-dense) are
kept unstacked.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common, moe
from repro.models.common import ParamBuilder
from repro.sharding.act_hints import hint_residual


def _head_dim(cfg) -> int:
    return cfg.head_dim or cfg.d_model // cfg.n_heads


def init_layer(cfg, key, is_moe: bool):
    b = ParamBuilder(key, dtype=cfg.np_dtype)
    d, hd = cfg.d_model, _head_dim(cfg)
    b.add("ln_attn", (d,), ("embed",), init="ones")
    b.add("wq", (d, cfg.n_heads * hd), ("embed", "heads"))
    b.add("wk", (d, cfg.n_kv_heads * hd), ("embed", "heads"))
    b.add("wv", (d, cfg.n_kv_heads * hd), ("embed", "heads"))
    b.add("wo", (cfg.n_heads * hd, d), ("heads", "embed"),
          scale=(cfg.n_heads * hd) ** -0.5)
    b.add("ln_mlp", (d,), ("embed",), init="ones")
    if is_moe:
        moe.init_moe(b, "moe", d, cfg.moe.d_expert, cfg.moe.n_experts,
                     cfg.moe.n_shared)
    else:
        b.add("mlp/gate", (d, cfg.d_ff), ("embed", "ff"))
        b.add("mlp/up", (d, cfg.d_ff), ("embed", "ff"))
        b.add("mlp/down", (cfg.d_ff, d), ("ff", "embed"),
              scale=cfg.d_ff ** -0.5)
    return b.params, b.axes


def init_lm(cfg, key):
    """Returns (params, logical_axes)."""
    ke, kl, kh, kp = jax.random.split(key, 4)
    b = ParamBuilder(ke, dtype=cfg.np_dtype)
    b.add("embed", (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
          scale=0.02)
    b.add("ln_f", (cfg.d_model,), ("embed",), init="ones")
    if not cfg.tie_embeddings:
        b.add("lm_head", (cfg.d_model, cfg.padded_vocab),
              ("embed", "vocab"))
    params, axes = b.params, b.axes

    n_dense_prefix = cfg.moe.first_dense if cfg.moe else 0
    n_scan = cfg.n_layers - n_dense_prefix
    keys = jax.random.split(kl, n_scan)
    layer_p = jax.vmap(
        lambda k: init_layer(cfg, k, is_moe=cfg.moe is not None)[0])(keys)
    _, layer_axes = common.eval_axes(
        lambda k: init_layer(cfg, k, is_moe=cfg.moe is not None), kh)
    params["layers"] = layer_p
    axes["layers"] = common.stack_layer_axes(layer_axes)
    if n_dense_prefix:
        pk = jax.random.split(kp, n_dense_prefix)
        for i in range(n_dense_prefix):
            pp, pa = init_layer(cfg, pk[i], is_moe=False)
            params[f"dense{i}"] = pp
            axes[f"dense{i}"] = pa
    return params, axes


# -- forward ------------------------------------------------------------------


def _attn_block(cfg, p, x, *, positions, layer_cache=None, layer=None,
                cache_update_rolling=False, window, return_kv=False):
    """Self-attention sublayer. Returns (out, new_cache_or_kv).

    In decode, ``layer`` (a traced index) marks ``layer_cache`` as the
    layer-stacked cache: the new K/V rows are written into layer
    ``layer`` of it in place, and the stacked cache is returned."""
    hd = _head_dim(cfg)
    h = common.rms_norm(x, p["ln_attn"], cfg.norm_eps)
    b, s, _ = h.shape
    q = jnp.einsum("bsd,dh->bsh", h, p["wq"]).reshape(
        b, s, cfg.n_heads, hd)
    k = jnp.einsum("bsd,dh->bsh", h, p["wk"]).reshape(
        b, s, cfg.n_kv_heads, hd)
    v = jnp.einsum("bsd,dh->bsh", h, p["wv"]).reshape(
        b, s, cfg.n_kv_heads, hd)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)

    if layer_cache is not None and s == 1:      # decode
        if layer is None:
            new = cache = attn.cache_update(layer_cache, k, v,
                                            rolling=cache_update_rolling)
        else:
            new, cache = attn.cache_append_layer(
                layer_cache, layer, k, v, rolling=cache_update_rolling)
        o = attn.decode_attention(q, cache, window=window,
                                  impl=cfg.decode_attn_impl)
    else:                                        # train / prefill
        o = attn.attention(q, k, v, causal=True, window=window,
                           block_q=cfg.block_q)
        new = (k, v) if return_kv else None
    o = o.reshape(b, s, cfg.n_heads * hd)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"]), new


def _ffn_block(cfg, p, x, is_moe: bool, serving: bool = False):
    h = common.rms_norm(x, p["ln_mlp"], cfg.norm_eps)
    if is_moe:
        out, aux = moe.apply_moe(p["moe"], h, top_k=cfg.moe.top_k,
                                 capacity_factor=cfg.moe.capacity_factor,
                                 full_capacity=serving)
        return out, aux
    return common.swiglu(h, p["mlp"]["gate"], p["mlp"]["up"],
                         p["mlp"]["down"]), {}


def _layer(cfg, p, x, *, positions, is_moe, layer_cache=None, layer=None,
           rolling=False, return_kv=False, serving=False):
    x = hint_residual(x)
    a, new_cache = _attn_block(
        cfg, p, x, positions=positions, layer_cache=layer_cache,
        layer=layer, cache_update_rolling=rolling, window=cfg.sliding_window,
        return_kv=return_kv)
    x = hint_residual(x + a)
    f, aux = _ffn_block(cfg, p, x, is_moe, serving=serving)
    return hint_residual(x + f), new_cache, aux


def forward(cfg, params, tokens, *, frontend=None, positions=None,
            remat: bool = False):
    """Training/scoring forward -> (logits, aux).

    ``frontend``: optional (B, F, d_model) stub embeddings (VLM/audio)
    prepended to the token embeddings."""
    x = common.embedding_lookup(params["embed"], tokens)
    if frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    is_moe = cfg.moe is not None

    def dense_block(p, x):
        y, _, aux = _layer(cfg, p, x, positions=positions, is_moe=False)
        return y, aux

    def scan_block(p, x):
        y, _, aux = _layer(cfg, p, x, positions=positions, is_moe=is_moe)
        return y, aux

    if remat:
        dense_block = jax.checkpoint(dense_block)
        scan_block = jax.checkpoint(scan_block)

    aux_acc = {}
    n_dense_prefix = cfg.moe.first_dense if is_moe else 0
    for i in range(n_dense_prefix):
        x, _ = dense_block(params[f"dense{i}"], x)

    def body(x, p):
        y, aux = scan_block(p, x)
        return y, aux.get("lb_loss", jnp.zeros((), jnp.float32))

    x, lb = jax.lax.scan(body, x, params["layers"])
    aux_acc["lb_loss"] = jnp.sum(lb)

    x = common.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return logits, aux_acc


def loss_fn(cfg, params, batch, *, remat: bool = False):
    logits, aux = forward(cfg, params, batch["tokens"],
                          frontend=batch.get("frontend"), remat=remat)
    n_front = 0 if batch.get("frontend") is None \
        else batch["frontend"].shape[1]
    logits = logits[:, n_front:]
    loss, metrics = common.cross_entropy_max_z(
        logits, batch["targets"], batch.get("mask"),
        z_weight=cfg.max_z_weight)
    if cfg.moe is not None:
        loss = loss + cfg.moe.lb_weight * aux["lb_loss"]
        metrics["lb_loss"] = aux["lb_loss"]
    metrics["loss"] = loss
    return loss, metrics


# -- serving ------------------------------------------------------------------
#
# The serving forward is organized around a STAGE-PARTITION seam: the
# scan-stacked layer block splits into K contiguous-layer stages, each
# with its own params slice and KV-cache slice, so a model can be
# served by a chain of machines (see ``serving/swarm_serve.py``). The
# single-host path is the K=1 specialization — ``prefill`` /
# ``decode_step`` are thin wrappers over ``stage_prefill`` /
# ``stage_decode`` with ``first=last=True``, so staged and monolithic
# serving share every op (bit-identical by construction).


def n_scan_layers(cfg) -> int:
    return cfg.n_layers - (cfg.moe.first_dense if cfg.moe else 0)


def stage_bounds(cfg, k_stages: int) -> list[tuple[int, int]]:
    """Contiguous partition of the scan-stacked layers into
    ``k_stages`` near-equal [lo, hi) ranges (remainder spread over the
    leading stages). Dense-prefix layers (DeepSeek first-layer-dense)
    ride with stage 0."""
    n = n_scan_layers(cfg)
    if not 1 <= k_stages <= n:
        raise ValueError(f"k_stages {k_stages} not in [1, {n}]")
    base, rem = divmod(n, k_stages)
    bounds, lo = [], 0
    for i in range(k_stages):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _slice_rows(leaf, lo: int, hi: int):
    if isinstance(leaf, jax.ShapeDtypeStruct):
        # abstract trees (jax.eval_shape) slice too, so a stage's
        # parameter STRUCTURE is available without materializing the
        # full model (the `like` for restoring published stage weights)
        return jax.ShapeDtypeStruct((hi - lo,) + tuple(leaf.shape[1:]),
                                    leaf.dtype)
    return leaf[lo:hi]


def slice_stage_params(cfg, params, lo: int, hi: int, *, first: bool,
                       last: bool):
    """The parameter subtree one stage needs: its layer-stack rows,
    plus the embedding (+ dense prefix) on the first stage and the
    final norm + head on the last (tied embeddings put the embedding
    matrix on the last stage too)."""
    sp = {"layers": jax.tree.map(lambda l: _slice_rows(l, lo, hi),
                                 params["layers"])}
    n_dense_prefix = cfg.moe.first_dense if cfg.moe else 0
    if first:
        sp["embed"] = params["embed"]
        for i in range(n_dense_prefix):
            sp[f"dense{i}"] = params[f"dense{i}"]
    if last:
        sp["ln_f"] = params["ln_f"]
        if cfg.tie_embeddings:
            sp["embed"] = params["embed"]
        else:
            sp["lm_head"] = params["lm_head"]
    return sp


def _init_cache_range(cfg, batch_size: int, max_len: int, lo: int,
                      hi: int, *, first: bool):
    hd = _head_dim(cfg)
    s_max = min(max_len, cfg.sliding_window) if cfg.sliding_window \
        else max_len
    n_dense_prefix = (cfg.moe.first_dense if cfg.moe else 0) if first \
        else 0
    n_scan = hi - lo

    def one(_):
        return attn.KVCache.init(batch_size, s_max, cfg.n_kv_heads, hd,
                                 dtype=cfg.np_dtype)

    scan_cache = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[one(i) for i in range(n_scan)]) \
        if n_scan else None
    prefix = [one(i) for i in range(n_dense_prefix)]
    return {"scan": scan_cache, "prefix": prefix}


def init_cache(cfg, batch_size: int, max_len: int):
    """Stacked per-layer KV cache (+ unstacked dense-prefix caches)."""
    return _init_cache_range(cfg, batch_size, max_len, 0,
                             n_scan_layers(cfg), first=True)


def init_stage_cache(cfg, batch_size: int, max_len: int, lo: int,
                     hi: int, *, first: bool):
    """Per-stage cache: KV stack for layers [lo, hi) (+ the dense
    prefix caches when this is the first stage)."""
    return _init_cache_range(cfg, batch_size, max_len, lo, hi,
                             first=first)


def _head_logits(cfg, params, x):
    """Final norm + LM head over (B, 1, D) -> (B, V)."""
    x = common.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    return jnp.einsum("bsd,dv->bsv", x, head)[:, 0]


def stage_prefill(cfg, params, inp, cache, *, first: bool, last: bool,
                  frontend=None, prompt_len=None):
    """Prefill one stage's layers over the full (right-padded) prompt.

    ``inp``: (B, S) tokens when ``first`` else (B, S, D) activations
    from the previous stage. Returns ``(out, cache)`` where ``out`` is
    the (B, V) last-token logits when ``last`` (gathered at each
    slot's true ``prompt_len - 1``) else the full-width (B, S, D)
    activations to stream to the next stage.

    ``prompt_len``: optional (B,) true per-slot prompt lengths. Prompts
    are then expected RIGHT-padded to the (bucketed) common width —
    causal attention never lets a real position see the pad tail — so
    the cache lengths are set per slot. This is what lets admission pad
    to power-of-two buckets (capping recompiles) without changing
    outputs.
    """
    if first:
        x = common.embedding_lookup(params["embed"], inp)
        if frontend is not None:
            x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    else:
        x = inp
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    is_moe = cfg.moe is not None
    rolling = cfg.sliding_window is not None
    scan_c = cache["scan"]
    if scan_c is None:
        s_max = 0
    elif isinstance(scan_c, attn.PagedKVCache):
        if rolling:
            raise ValueError(
                "direct paged SWA prefill unsupported — prefill dense "
                "scratch and paginate (serving.paging)")
        s_max = scan_c.s_max
    else:
        s_max = scan_c.k.shape[2]

    def write(cache_layer, kv):
        k, v = kv
        if rolling and (prompt_len is not None or s > s_max):
            # treat an un-annotated over-length prefill as full-width
            # prompts (the seed's slice-and-bump write clamped the
            # wrapped dynamic_update_slice to offset 0, scrambling
            # cell->position mapping — caught by teacher-forcing tests)
            eff_len = (prompt_len if prompt_len is not None
                       else jnp.full((b,), s, jnp.int32))
            # per-slot ring placement: cell c must hold the newest
            # prompt position p == c (mod s_max), i.e.
            # p = len-1 - ((len-1-c) mod s_max); cells a short slot
            # never wrote clamp to garbage rows that stay masked.
            # This is exact for ANY right-padded width — a batched
            # wave prefill can mix slots shorter and longer than the
            # ring.
            cell = jnp.arange(s_max)[None, :]
            plen = eff_len.astype(jnp.int32)[:, None]
            src = jnp.clip(plen - 1 - ((plen - 1 - cell) % s_max),
                           0, s - 1)[:, :, None, None]
            return cache_layer._replace(
                k=jnp.take_along_axis(k, src, axis=1).astype(
                    cache_layer.k.dtype),
                v=jnp.take_along_axis(v, src, axis=1).astype(
                    cache_layer.v.dtype),
                length=jnp.broadcast_to(eff_len.astype(jnp.int32),
                                        cache_layer.length.shape))
        new = attn.cache_update(cache_layer, k, v)
        if prompt_len is not None:
            # pad-tail cells stay garbage; masked by length and
            # overwritten as decode advances
            new = new._replace(
                length=jnp.broadcast_to(prompt_len.astype(jnp.int32),
                                        new.length.shape))
        return new

    new_prefix = []
    for i in range(len(cache["prefix"])):
        x, kv, _ = _layer(cfg, params[f"dense{i}"], x,
                          positions=positions, is_moe=False,
                          return_kv=True, serving=True)
        new_prefix.append(write(cache["prefix"][i], kv))

    def body(x, pc):
        p, c = pc
        y, kv, _ = _layer(cfg, p, x, positions=positions, is_moe=is_moe,
                          return_kv=True, serving=True)
        return y, write(c, kv)

    x, new_scan = jax.lax.scan(body, x, (params["layers"],
                                         cache["scan"]))
    new_cache = {"scan": new_scan, "prefix": new_prefix}
    if not last:
        return x, new_cache
    if prompt_len is None:
        x_last = x[:, -1:]
    else:
        idx = (prompt_len.astype(jnp.int32) - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)
    return _head_logits(cfg, params, x_last), new_cache


def stage_decode(cfg, params, inp, cache, *, first: bool, last: bool):
    """One decode step through one stage's layers.

    ``inp``: (B, 1) token ids when ``first`` else (B, 1, D) activations.
    Returns ``(out, cache)``: (B, V) logits when ``last`` else (B, 1, D)
    activations. Positions come from the PER-SLOT cache lengths, so
    slots at different depths (continuous batching) each get the right
    RoPE phase — and every stage derives them independently from its
    own cache, which stays consistent across a chain because all
    stages advance in lockstep.

    The stacked cache ``cache["scan"]`` (dense or paged) rides the layer
    scan as its CARRY: each layer writes its B new K/V rows into its
    index of the stack in place (``attn.cache_append_layer``) and reads
    its own slabs back for the attention kernel, and the lengths advance
    once after the scan. No step rebuilds the stack; under a jit that
    donates the cache the whole step is in place."""
    x = common.embedding_lookup(params["embed"], inp) if first else inp
    is_moe = cfg.moe is not None
    rolling = cfg.sliding_window is not None
    length = (cache["scan"].length[0] if cache["scan"] is not None
              else cache["prefix"][0].length)          # (B,)
    positions = length[:, None].astype(jnp.int32)

    new_prefix = []
    for i in range(len(cache["prefix"])):
        x2, c, _ = _layer(cfg, params[f"dense{i}"], x,
                          positions=positions, is_moe=False,
                          layer_cache=cache["prefix"][i], rolling=rolling,
                          serving=True)
        x = x2
        new_prefix.append(c)

    def body(carry, pl):
        x, c = carry
        p, layer = pl
        y, c, _ = _layer(cfg, p, x, positions=positions, is_moe=is_moe,
                         layer_cache=c, layer=layer, rolling=rolling,
                         serving=True)
        return (y, c), None

    n_scan = jax.tree.leaves(params["layers"])[0].shape[0]
    (x, new_scan), _ = jax.lax.scan(
        body, (x, cache["scan"]), (params["layers"], jnp.arange(n_scan)))
    if new_scan is not None:
        new_scan = new_scan._replace(length=new_scan.length + 1)
    new_cache = {"scan": new_scan, "prefix": new_prefix}
    if not last:
        return x, new_cache
    return _head_logits(cfg, params, x), new_cache


def prefill_extend(cfg, params, tokens, cache, *, start, seg_len):
    """Chunked prefill: run one (right-padded) prompt SEGMENT at
    absolute offset ``start`` against an already-partial cache.

    ``tokens``: (B, S) segment, right-padded; ``start``: scalar int32,
    absolute position of tokens[:, 0] — must equal the cache's current
    per-slot ``length`` (the write cursor); ``seg_len``: scalar int32,
    true segment length (<= S). Returns (last-token logits (B, V),
    cache with ``length = start + seg_len``).

    Every query row recomputes its FULL softmax over the whole live
    prefix (earlier segments read back from the cache + this segment's
    fresh K/V) — no online-softmax splitting — so chaining segments
    reproduces the single-shot ``prefill`` exactly, which is what lets
    prompts exceed one dense prefill bucket (paged caches: exceed
    ``max_len`` entirely) and lets shared-prefix admission resume after
    a content-addressed prefix hit. Full-causal only: an SWA ring has
    no stable absolute cells to resume into."""
    if cfg.sliding_window is not None:
        raise ValueError("prefill_extend is full-causal only (SWA "
                         "rings roll; resume offsets are undefined)")
    x = common.embedding_lookup(params["embed"], tokens)
    b, s, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    seg_len = jnp.asarray(seg_len, jnp.int32)
    positions = jnp.broadcast_to(start + jnp.arange(s)[None], (b, s))
    is_moe = cfg.moe is not None
    hd = _head_dim(cfg)

    def ext_layer(p, x, layer_cache, moe_layer: bool):
        x = hint_residual(x)
        h = common.rms_norm(x, p["ln_attn"], cfg.norm_eps)
        q = jnp.einsum("bsd,dh->bsh", h, p["wq"]).reshape(
            b, s, cfg.n_heads, hd)
        k = jnp.einsum("bsd,dh->bsh", h, p["wk"]).reshape(
            b, s, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,dh->bsh", h, p["wv"]).reshape(
            b, s, cfg.n_kv_heads, hd)
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
        new = attn.cache_update(layer_cache, k, v)   # writes at length
        view = attn.paged_view(new) \
            if isinstance(new, attn.PagedKVCache) else new
        o = attn.attention(q, view.k, view.v, causal=True,
                           q_offset=start, block_q=cfg.block_q)
        a = jnp.einsum("bsh,hd->bsd",
                       o.reshape(b, s, cfg.n_heads * hd), p["wo"])
        x = hint_residual(x + a)
        f, _ = _ffn_block(cfg, p, x, moe_layer, serving=True)
        return hint_residual(x + f), new

    new_prefix = []
    for i in range(len(cache["prefix"])):
        x, c = ext_layer(params[f"dense{i}"], x, cache["prefix"][i],
                         False)
        new_prefix.append(c)

    def body(x, pc):
        p, c = pc
        y, new_c = ext_layer(p, x, c, is_moe)
        return y, new_c

    x, new_scan = jax.lax.scan(body, x, (params["layers"],
                                         cache["scan"]))

    def fix_len(c):
        # cache_update advanced length by the PADDED width; the true
        # cursor is start + seg_len
        return c._replace(length=jnp.broadcast_to(
            (start + seg_len).astype(jnp.int32), c.length.shape))

    is_cache = lambda c: isinstance(c, (attn.KVCache, attn.PagedKVCache))
    new_cache = jax.tree.map(fix_len,
                             {"scan": new_scan, "prefix": new_prefix},
                             is_leaf=is_cache)
    idx = jnp.broadcast_to(jnp.reshape(seg_len, (1, 1, 1)) - 1,
                           (b, 1, 1))
    x_last = jnp.take_along_axis(x, idx, axis=1)
    return _head_logits(cfg, params, x_last), new_cache


def prefill(cfg, params, tokens, cache, *, frontend=None,
            prompt_len=None):
    """Run the full prompt, fill the cache -> (last-token logits,
    cache). The K=1 stage specialization — see ``stage_prefill``."""
    return stage_prefill(cfg, params, tokens, cache, first=True,
                         last=True, frontend=frontend,
                         prompt_len=prompt_len)


def decode_step(cfg, params, token, cache):
    """One decode step. token: (B, 1) -> (logits (B, V), cache). The
    K=1 stage specialization — see ``stage_decode``."""
    return stage_decode(cfg, params, token, cache, first=True,
                        last=True)
