"""Weights made by the benchmark from ``--seed``: the whole parameter tree
in one jitted call on the device, in the type it is served in.

The tree's structure (names, shapes, dtypes) is the model's parameter
layout, read with ``jax.eval_shape`` of its init; the values are the
benchmark's own, so the plain reference can be given the very same
weights without taking anything the program made. ``init`` in the
configuration file names the leaves set to ones or zeros and the fixed
scales; every other leaf is normal with standard deviation
``fan_in ** -0.5``, where the fan-in is the second-to-last dimension
(stacked layers lead).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import harness


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "name", "")))


def _maker(shapes, init: dict):
    ones, zeros = set(init.get("ones", ())), set(init.get("zeros", ()))
    scale = dict(init.get("scale", {}))
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree.structure(shapes)

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = _leaf_name(path)
            if name in ones:
                leaves.append(jnp.ones(s.shape, s.dtype))
            elif name in zeros:
                leaves.append(jnp.zeros(s.shape, s.dtype))
            else:
                if name in scale:
                    std = float(scale[name])
                elif len(s.shape) >= 2:
                    std = float(s.shape[-2]) ** -0.5
                else:
                    raise ValueError(f"no init rule for 1-D leaf {name!r}")
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                      jnp.float32) * std
                leaves.append(x.astype(s.dtype))
        return jax.tree.unflatten(treedef, leaves)

    return make


def make(shapes, init: dict, seed: int, device=None):
    """The weights for ``shapes`` (a tree of ShapeDtypeStructs) from
    ``seed``, made on ``device`` in one jitted call."""
    key = harness.seed_key(seed, 0x5EED)
    fn = jax.jit(_maker(shapes, init))
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)
