"""Jit'd public wrappers over the int8 quantization kernels.

``impl`` selects the backend:
  * ``"pallas"`` — the TPU Pallas kernels (interpret mode off-TPU),
  * ``"jnp"``    — the pure-jnp oracle (used for dry-run lowering so the
                   quantization FLOPs/bytes stay visible/analyzable in HLO,
                   and on hosts where interpret mode would be too slow).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import int8_quant, ref

Quantized = ref.Quantized


def quantize(x: jnp.ndarray, *, impl: str = "pallas") -> Quantized:
    """Paper-faithful int8 quantization (6-sigma clip, bucket-mean codebook)."""
    if impl == "jnp":
        return ref.quantize(x)
    lo, width = int8_quant.quant_range(x)
    codes, sums, counts = int8_quant.encode_hist(x, lo, width)
    return Quantized(codes, ref.make_codebook(sums, counts, lo, width))


@functools.partial(jax.jit, static_argnames=("impl",))
def _quantize_pseudograd(anchor, theta, scale, *, impl: str):
    af = anchor.astype(jnp.float32)
    tf = theta.astype(jnp.float32)
    if impl == "jnp":
        return ref.quantize_pseudograd(af, tf, scale=scale)
    # the pseudo-gradient scale*(anchor - theta) is never materialized
    # in HBM: the range kernel reads (anchor, theta) twice (mean, then
    # centered sum of squares), the fused encode once more and emits
    # codes + histogram.
    lo, width = int8_quant.pseudograd_quant_range(af, tf, scale=scale)
    codes, sums, counts = int8_quant.pseudograd_encode_hist(
        anchor, theta, lo, width, scale=scale)
    return Quantized(codes, ref.make_codebook(sums, counts, lo, width))


def quantize_pseudograd(anchor: jnp.ndarray, theta: jnp.ndarray, *,
                        scale=None, impl: str = "pallas") -> Quantized:
    """Fused ``scale * (anchor - theta)`` + quantize, single HBM trip per
    input — bit-identical to ``quantize(scale * (anchor - theta))``
    (``scale=None`` means unscaled; it is the elastic worker weight when
    the ring's transmit path calls this)."""
    return _quantize_pseudograd(anchor, theta, scale, impl=impl)


def dequantize(q: Quantized, *, dtype=jnp.float32,
               impl: str = "pallas") -> jnp.ndarray:
    if impl == "jnp":
        return ref.dequantize(q, dtype)
    return int8_quant.decode(q.codes, q.codebook).astype(dtype)


def dequantize_add(q: Quantized, acc: jnp.ndarray, *,
                   impl: str = "pallas") -> jnp.ndarray:
    """acc + dequantize(q) — fused on the Pallas path."""
    if impl == "jnp":
        return acc + ref.dequantize(q, acc.dtype)
    return int8_quant.decode_add(q.codes, q.codebook, acc)


def roundtrip_error(x: jnp.ndarray, *, impl: str = "jnp") -> jnp.ndarray:
    """Max |x - deq(q(x))| inside the clip range — test/bench helper."""
    q = quantize(x, impl=impl)
    return jnp.max(jnp.abs(x.astype(jnp.float32) - dequantize(q, impl=impl)))
