"""Plain decoder-only transformer with grouped-query attention
(InternLM2, arXiv:2403.17297): pre-norm RMSNorm blocks of causal
self-attention with rotary positions (rotate-half, base ``rope_theta``)
and a SwiGLU MLP, a final RMSNorm and an untied LM head.

Full causal attention over the whole row, with no cache, blocks or
kernels. Parameters are read by name from the model's layout
(``embed``, ``ln_f``, ``lm_head``, and per layer ``ln_attn``, ``wq``,
``wk``, ``wv``, ``wo``, ``ln_mlp``, ``mlp/<gate, up, down>``, stacked).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.lowp import act, mm, rms_norm


def _rope(x, theta: float):
    """x (S, H, hd): rotate-half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg, p, x, prec):
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    s = x.shape[0]
    h = rms_norm(x, p["ln_attn"], cfg["rms_norm_eps"])
    q = _rope(mm("sd,df->sf", h, p["wq"], prec).reshape(s, nh, hd),
              cfg["rope_theta"])
    k = _rope(mm("sd,df->sf", h, p["wk"], prec).reshape(s, nkv, hd),
              cfg["rope_theta"])
    v = mm("sd,df->sf", h, p["wv"], prec).reshape(s, nkv, hd)
    g = nh // nkv
    k = jnp.repeat(k, g, axis=1)          # query head i reads kv head i//g
    v = jnp.repeat(v, g, axis=1)
    scores = mm("thd,shd->hts", q, k, prec) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = mm("hts,shd->thd", w, v, prec).reshape(s, nh * hd)
    x = act(x + mm("sf,fd->sd", o, p["wo"], prec), prec)
    h = rms_norm(x, p["ln_mlp"], cfg["rms_norm_eps"])
    gate = mm("sd,df->sf", h, p["mlp"]["gate"], prec)
    up = mm("sd,df->sf", h, p["mlp"]["up"], prec)
    return act(x + mm("sf,fd->sd", jax.nn.silu(gate) * up,
                      p["mlp"]["down"], prec), prec)


def logits_at(cfg, params, tokens, positions, prec: str = "f32"):
    """Logits (len(positions), V) at ``positions`` of one token row."""
    x = act(params["embed"][tokens].astype(jnp.float32), prec)

    def body(x, p):
        return _block(cfg, p, x, prec), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(body, x, params["layers"])
        x = rms_norm(x[positions], params["ln_f"], cfg["rms_norm_eps"])
        return act(mm("sd,dv->sv", x, params["lm_head"], prec), prec)
