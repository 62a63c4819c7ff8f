"""Plain DiLoCo (INTELLECT-1 Alg. 1) around a reference model's loss.

Each of ``k`` workers starts an outer step from the anchor, stored in
the parameter type the configuration states, and takes ``H`` AdamW
steps (float32 moments, the update computed in float32 and stored back
in the parameter type). The pseudo-gradients ``anchor - theta_i`` are
averaged exactly in float32 (the program's int8 ring rounds each element
to the mean of its bucket, one of 256; PERF.md gives the look at what
that moves), and one Nesterov step updates the float32 anchor; every
worker restarts from it.

``fault`` plants one of the faults the comparison has to catch, in this
reference put in the program's place: ``half_batch`` takes the loss
mean over the first half of each row only, ``no_exchange`` applies
worker 0's pseudo-gradient alone in place of the average.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(tree) -> list[float]:
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
        for l in jax.tree.leaves(t)])(tree)]


def _adamw(p, g, m, v, step, opt):
    b1, b2 = opt["b1"], opt["b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    pf = p.astype(jnp.float32)
    new = pf - opt["lr"] * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                            + opt["weight_decay"] * pf)
    return new.astype(p.dtype), m, v


def _worker_phase(loss_fn, opt, params, m, v, step0, batches, fault):
    """H AdamW steps of one worker; returns the new state and the loss
    of every step."""
    def body(carry, batch):
        p, m, v, step = carry
        mask = batch["mask"]
        if fault == "half_batch":
            half = mask.shape[-1] // 2
            mask = mask.at[..., half:].set(0.0)
        lval, g = jax.value_and_grad(loss_fn)(
            p, batch["tokens"], batch["targets"], mask)
        step = step + 1.0
        out = jax.tree.map(
            lambda p_, g_, m_, v_: _adamw(p_, g_, m_, v_, step, opt),
            p, g, m, v)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        return (pick(0), pick(1), pick(2), step), lval

    (p, m, v, step), losses = jax.lax.scan(
        body, (params, m, v, step0), batches)
    return p, m, v, step, losses


def run(loss_fn, params0, feed, *, k: int, h: int, n_steps: int,
        opt: dict, outer: dict, fault: str | None = None) -> dict:
    """``n_steps`` outer steps from ``params0`` on the batches
    ``feed(global_step, h, k)``. Returns the loss of every inner step
    (per outer step, a (k, h) list), the leaf norms of the first
    averaged pseudo-gradient (what the outer optimizer gets at step 1,
    its momentum after it) and of the anchor's change after the
    ``n_steps``."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    anchor = f32(params0)
    mom = jax.tree.map(jnp.zeros_like, anchor)
    zeros = jax.tree.map(
        lambda x: jnp.zeros((k,) + x.shape, jnp.float32), params0)
    m, v = zeros, jax.tree.map(jnp.copy, zeros)
    step = jnp.zeros((k,), jnp.float32)
    dtypes = jax.tree.map(lambda x: x.dtype, params0)

    phase = jax.jit(jax.vmap(
        functools.partial(_worker_phase, loss_fn, opt, fault=fault),
        in_axes=(0, 0, 0, 0, 1)))

    @jax.jit
    def outer_step(anchor, mom, thetas):
        deltas = jax.tree.map(lambda a, t: a[None] - t.astype(jnp.float32),
                              anchor, thetas)
        if fault == "no_exchange":
            dbar = jax.tree.map(lambda d: d[0], deltas)
        else:
            dbar = jax.tree.map(lambda d: d.mean(0), deltas)
        mu, lr = outer["momentum"], outer["lr"]
        mom = jax.tree.map(lambda mo, d: mu * mo + d, mom, dbar)
        anchor = jax.tree.map(lambda a, mo, d: a - lr * (mu * mo + d),
                              anchor, mom, dbar)
        return anchor, mom, dbar

    losses, grad_norms = [], None
    for t in range(n_steps):
        thetas = jax.tree.map(
            lambda a, dt: jnp.broadcast_to(a.astype(dt)[None],
                                           (k,) + a.shape),
            anchor, dtypes)
        batches = feed(t * h, h, k)
        thetas, m, v, step, lw = phase(thetas, m, v, step, batches)
        losses.append(np.asarray(lw, np.float64).tolist())
        anchor, mom, dbar = outer_step(anchor, mom, thetas)
        if t == 0:
            grad_norms = leaf_norms(dbar)
        del thetas, dbar
    change = leaf_norms(jax.tree.map(lambda a, p: a - p.astype(jnp.float32),
                                     anchor, params0))
    return {"step_losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
