"""Pure-jnp reference (oracle) for the PRIME int8 quantization scheme.

Paper (INTELLECT-1 §2.2): uniform quantization with clipping, following
Ryabinin et al. (2020):

  1. compute mean (mu) and std (sigma) of the tensor,
  2. quantization range = [mu - 6 sigma, mu + 6 sigma],
  3. range divided uniformly into 256 buckets,
  4. codebook value per bucket = average of the values falling in it
     (empty buckets fall back to the bucket midpoint),
  5. reduction is performed in fp32 -- only the *wire format* is int8
     (Q(a) + Q(b) != Q(a + b)).

Everything here is plain jnp and serves as the allclose oracle for the
Pallas kernels in ``int8_quant.py``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

NUM_BUCKETS = 256
CLIP_SIGMAS = 6.0
EPS = 1e-12


class Quantized(NamedTuple):
    """Wire format of one quantized tensor (or tensor chunk)."""

    codes: jnp.ndarray      # uint8, same shape as the input
    codebook: jnp.ndarray   # (256,) fp32 dequantization table

    @property
    def wire_bytes(self) -> int:
        """Bytes on the wire: 1 byte/element + the fp32 codebook sideband."""
        return int(self.codes.size) + 4 * NUM_BUCKETS


def quant_params(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, bucket_width) of the clipped uniform quantization range."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf)
    sigma = jnp.std(xf)
    half = CLIP_SIGMAS * sigma
    lo = mu - half
    width = jnp.maximum(2.0 * half / NUM_BUCKETS, EPS)
    return lo, width


def encode(x: jnp.ndarray, lo: jnp.ndarray, width: jnp.ndarray) -> jnp.ndarray:
    """Bucket indices (uint8) for every element of ``x``."""
    xf = x.astype(jnp.float32)
    idx = jnp.floor((xf - lo) / width)
    return jnp.clip(idx, 0, NUM_BUCKETS - 1).astype(jnp.uint8)


def bucket_stats(
    x: jnp.ndarray, codes: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-bucket (sum, count) of the values mapped to each bucket."""
    xf = x.astype(jnp.float32).reshape(-1)
    c = codes.reshape(-1).astype(jnp.int32)
    sums = jnp.zeros((NUM_BUCKETS,), jnp.float32).at[c].add(xf)
    # counted in int32: a float32 count stops growing at 2^24
    counts = jnp.zeros((NUM_BUCKETS,), jnp.int32).at[c].add(1)
    return sums, counts.astype(jnp.float32)


def make_codebook(
    sums: jnp.ndarray, counts: jnp.ndarray, lo: jnp.ndarray, width: jnp.ndarray
) -> jnp.ndarray:
    """Bucket means; empty buckets fall back to the bucket midpoint."""
    centers = lo + (jnp.arange(NUM_BUCKETS, dtype=jnp.float32) + 0.5) * width
    means = sums / jnp.maximum(counts, 1.0)
    return jnp.where(counts > 0, means, centers)


def quantize(x: jnp.ndarray) -> Quantized:
    """Full paper-faithful quantization: codes + bucket-mean codebook."""
    lo, width = quant_params(x)
    codes = encode(x, lo, width)
    sums, counts = bucket_stats(x, codes)
    return Quantized(codes, make_codebook(sums, counts, lo, width))


def dequantize(q: Quantized, dtype=jnp.float32) -> jnp.ndarray:
    return q.codebook[q.codes.astype(jnp.int32)].astype(dtype)


def quantize_pseudograd(anchor: jnp.ndarray, theta: jnp.ndarray,
                        scale=None) -> Quantized:
    """Fused pseudo-gradient ``scale * (anchor - theta)`` + quantize —
    oracle for the fused Pallas kernel. ``scale`` is the elastic worker
    weight folded into the transmit path (None = unweighted)."""
    pg = anchor.astype(jnp.float32) - theta.astype(jnp.float32)
    if scale is not None:
        pg = pg * jnp.float32(scale)
    return quantize(pg)
