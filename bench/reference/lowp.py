"""Matmuls and stored values of the references at a stated precision.

The controls' precisions are one step below the bfloat16 the
configurations state, applied both ways as low-precision training does
it: matmul operands and stored activations are rounded in the forward
pass, and the cotangent that reaches each matmul's output is rounded
before the backward matmuls use it. ``fp8`` rounds to e4m3 forward and
e5m2 backward; ``int8`` to 255 symmetric levels both ways (the v5e's
integer matmul path). Each rounding has one scale for the tensor (its
largest magnitude maps to the format's largest value); accumulation
stays in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0      # largest finite float8_e4m3fn
E5M2_MAX = 57344.0    # largest finite float8_e5m2


def _round(x, dtype, fmax: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / fmax
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _round_int8(x) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


FORWARD = {"fp8": lambda x: _round(x, jnp.float8_e4m3fn, E4M3_MAX),
           "int8": _round_int8}
BACKWARD = {"fp8": lambda g: _round(g, jnp.float8_e5m2, E5M2_MAX),
            "int8": _round_int8}


def low(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``x`` rounded to ``precision`` forward; its gradient passes
    straight through."""
    x = x.astype(jnp.float32)
    return x + jax.lax.stop_gradient(FORWARD[precision](x) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def low_grad(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    """The identity; its cotangent is rounded to ``precision``."""
    return x


def _low_grad_fwd(x, precision):
    return x, None


def _low_grad_bwd(precision, _, g):
    return (BACKWARD[precision](g),)


low_grad.defvjp(_low_grad_fwd, _low_grad_bwd)


def act(x, precision: str = "f32") -> jnp.ndarray:
    """A value the model stores between operations (residual stream,
    logits), held at ``precision``: float32, or a control's lower one
    (where the program holds bfloat16)."""
    return x if precision == "f32" else low(x, precision)


def mm(spec: str, a, b, precision: str = "f32") -> jnp.ndarray:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision != "f32" and precision not in FORWARD:
        raise ValueError(f"unknown precision {precision!r}")
    if precision != "f32":
        a, b = low(a, precision), low(b, precision)
    out = jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return out if precision == "f32" else low_grad(out, precision)


def rms_norm(x, gamma, eps: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * gamma.astype(jnp.float32))
