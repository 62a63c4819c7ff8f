"""Plain Mamba-2 language model (arXiv:2405.21060): pre-norm residual
blocks of input projections, a causal depthwise convolution, the
selective state-space map and a gated RMSNorm, then a tied LM head.

The state-space map is computed in its quadratic (dual) form over the
whole sequence: ``y_t = sum_{s<=t} C_t . B_s exp(sum_{s<r<=t} dt_r A)
dt_s x_s + D x_t``, with no chunking, scan or cache. Parameters are
read by name from the model's layout (``embed``, ``ln_f``, and per layer
``mamba/ln`` and ``mamba/mamba/<in_z, in_x, in_b, in_c, in_dt, conv_x,
conv_b, conv_c, a_log, dt_bias, d_skip, norm, out>``, stacked).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.lowp import act, mm, rms_norm


def _conv(x, w):
    """Causal depthwise convolution, then SiLU. x (S, C), w (K, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    out = sum(xp[i:i + x.shape[0]] * w[i].astype(jnp.float32)
              for i in range(k))
    return jax.nn.silu(out)


def _ssm(x, dt, a, b, c, prec):
    """x (S, H, P), dt (S, H), a (H,), b/c (S, N) -> y (S, H, P)."""
    s = x.shape[0]
    cum = jnp.cumsum(dt * a[None, :], axis=0)                # (S, H)
    diff = cum[:, None, :] - cum[None, :, :]                 # (t, s, H)
    causal = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = mm("tn,sn->ts", c, b, prec)                         # (t, s)
    m = cb[:, :, None] * decay                               # (t, s, H)
    return mm("tsh,shp->thp", m, x * dt[:, :, None], prec)


def _block(cfg, p, x, prec):
    d_inner = cfg["expand"] * cfg["d_model"]
    hd = cfg["headdim"]
    n_heads = d_inner // hd
    q = p["mamba"]
    h = rms_norm(x, p["ln"], cfg["norm_eps"])
    z = mm("sd,df->sf", h, q["in_z"], prec)
    xs = _conv(mm("sd,df->sf", h, q["in_x"], prec), q["conv_x"])
    bs = _conv(mm("sd,df->sf", h, q["in_b"], prec), q["conv_b"])
    cs = _conv(mm("sd,df->sf", h, q["in_c"], prec), q["conv_c"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, q["in_dt"], prec)
                         + q["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(q["a_log"].astype(jnp.float32))
    xh = xs.reshape(-1, n_heads, hd)
    y = _ssm(xh, dt, a, bs, cs, prec)
    y = y + xh * q["d_skip"].astype(jnp.float32)[None, :, None]
    y = rms_norm(y.reshape(-1, d_inner) * jax.nn.silu(z), q["norm"],
                 cfg["norm_eps"])
    return act(x + mm("sf,fd->sd", y, q["out"], prec), prec)


def hidden(cfg, params, tokens, prec: str = "f32"):
    """Final hidden states (S, d) of one token row (S,)."""
    x = act(params["embed"][tokens].astype(jnp.float32), prec)

    def body(x, p):
        return jax.checkpoint(lambda p, x: _block(cfg, p, x, prec))(p, x), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(body, x, params["mamba"])
    return rms_norm(x, params["ln_f"], cfg["norm_eps"])


def loss(cfg, params, tokens, targets, mask, prec: str = "f32"):
    """Mean over masked positions of cross-entropy plus the max-z term
    ``z_weight * logsumexp^2`` (INTELLECT-1's auxiliary loss), over the
    rows of ``tokens`` (R, S)."""
    def row(t, y, m):
        x = hidden(cfg, params, t, prec)
        logits = act(mm("sd,vd->sv", x, params["embed"], prec), prec)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        tok = lse - ll + cfg["z_loss_weight"] * lse * lse
        return (tok * m).sum(), m.sum()

    num, den = jax.vmap(row)(tokens, targets, mask.astype(jnp.float32))
    return num.sum() / jnp.maximum(den.sum(), 1.0)
