"""Pallas int8 quantization kernels vs the pure-jnp oracle:
shape/dtype sweeps + hypothesis property tests of the paper's scheme."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo_compat import given, settings, st

from repro.kernels import int8_quant, ops, ref

SHAPES = [(16,), (1000,), (128, 128), (257, 130), (8, 4, 33),
          (3, 5, 7, 11), (65537,)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_encode_matches_ref(shape, dtype, rng):
    """The range kernel sums in its own fixed order, so its (lo, width)
    may differ from ``ref.quant_params`` in the last float32 bits (both
    are within ~2e-7 of float64): that is checked in bucket widths, and
    the codes and codebook against the reference on the kernel's range
    (a last-bit range change can move an element sitting on a bucket
    edge, about one in 10^5)."""
    x = jnp.asarray(rng.normal(1.5, 2.0, size=shape), dtype)
    lo_r, w_r = ref.quant_params(x)
    lo, w = int8_quant.quant_range(x)
    assert abs(float(lo) - float(lo_r)) <= 1e-4 * float(w_r)
    assert abs(float(w) - float(w_r)) <= 1e-6 * float(w_r)
    codes = ref.encode(x, lo, w)
    qr = ref.Quantized(codes, ref.make_codebook(
        *ref.bucket_stats(x, codes), lo, w))
    qp = ops.quantize(x, impl="pallas")
    np.testing.assert_array_equal(np.asarray(qr.codes),
                                  np.asarray(qp.codes))
    np.testing.assert_allclose(np.asarray(qr.codebook),
                               np.asarray(qp.codebook),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("fused", [False, True])
def test_vmapped_quantize_matches_per_row(fused):
    """A vmapped batch of rows quantizes each row to the same bits as a
    call on that row alone (the batched kernels' first/last-step
    bookkeeping follows the row's own grid axis)."""
    r = np.random.default_rng(5)
    n = int8_quant.BLOCK_ROWS * int8_quant.LANE + 1000
    a = jnp.asarray(r.normal(size=(3, n)), jnp.float32)
    t = jnp.asarray(r.normal(0.1, 0.5, size=(3, n)), jnp.float32)
    w = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)
    if fused:
        f = lambda a, t, w: ops.quantize_pseudograd(  # noqa: E731
            a, t, scale=w, impl="pallas")
    else:
        f = lambda a, t, w: ops.quantize(  # noqa: E731
            (a - t) * w, impl="pallas")
    batched = jax.vmap(f)(a, t, w)
    for i in range(3):
        one = f(a[i], t[i], w[i])
        np.testing.assert_array_equal(np.asarray(batched.codes[i]),
                                      np.asarray(one.codes))
        np.testing.assert_array_equal(np.asarray(batched.codebook[i]),
                                      np.asarray(one.codebook))


@pytest.mark.parametrize("shape", SHAPES)
def test_decode_matches_ref(shape, rng):
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = ref.quantize(x)
    dr = ref.dequantize(q)
    dp = ops.dequantize(q, impl="pallas")
    np.testing.assert_allclose(np.asarray(dr), np.asarray(dp),
                               rtol=1e-5, atol=1e-5)


def test_fused_pseudograd(rng):
    a = jnp.asarray(rng.normal(size=(300, 40)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(300, 40)), jnp.float32)
    qf = ops.quantize_pseudograd(a, t, impl="pallas")
    qr = ref.quantize_pseudograd(a, t)
    np.testing.assert_array_equal(np.asarray(qf.codes),
                                  np.asarray(qr.codes))


def test_decode_add_fused(rng):
    x = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    acc = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    q = ref.quantize(x)
    fused = ops.dequantize_add(q, acc, impl="pallas")
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(acc + ref.dequantize(q)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fused", [False, True])
def test_tail_mask_mid_slab(fused):
    """A flat size whose tail ends mid-slab in the second block: every
    valid element is counted once, no padding element is, and codes and
    counts match the reference exactly (the mask compares int32 element
    indices with an int32 ``nvalid``)."""
    n = (int8_quant.BLOCK_ROWS * int8_quant.LANE
         + 3 * int8_quant.SLAB_ROWS * int8_quant.LANE
         + 37 * int8_quant.LANE + 55)
    r = np.random.default_rng(11)
    a = jnp.asarray(r.normal(size=n), jnp.float32)
    t = jnp.asarray(r.normal(0.2, 1.0, size=n), jnp.float32)
    x = a - t if fused else a
    lo, width = ref.quant_params(x)
    if fused:
        codes, sums, counts = int8_quant.pseudograd_encode_hist(
            a, t, lo, width)
    else:
        codes, sums, counts = int8_quant.encode_hist(x, lo, width)
    sums_r, counts_r = ref.bucket_stats(x, ref.encode(x, lo, width))
    np.testing.assert_array_equal(np.asarray(codes),
                                  np.asarray(ref.encode(x, lo, width)))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_r))
    assert float(jnp.sum(counts)) == n
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_r),
                               rtol=1e-4, atol=1e-3)


# -- paper-scheme properties ---------------------------------------------------


@settings(deadline=None, max_examples=30)
@given(st.integers(10, 4000), st.floats(-5, 5), st.floats(0.01, 10),
       st.integers(0, 2**31 - 1))
def test_roundtrip_error_bounded_by_bucket_width(n, mu, sigma, seed):
    """Inside the 6-sigma clip range, |x - deq(q(x))| <= bucket width
    (bucket means can sit anywhere inside the bucket)."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(mu, sigma, size=n), jnp.float32)
    lo, width = ref.quant_params(x)
    q = ref.quantize(x)
    deq = ref.dequantize(q)
    hi = lo + ref.NUM_BUCKETS * width
    inside = (x >= lo) & (x < hi)
    err = jnp.abs(deq - x)
    assert float(jnp.max(jnp.where(inside, err, 0.0))) <= \
        float(width) + 1e-6


@settings(deadline=None, max_examples=30)
@given(st.integers(2, 500), st.integers(0, 2**31 - 1))
def test_codebook_values_inside_buckets(n, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=n) * r.uniform(0.1, 4), jnp.float32)
    lo, width = ref.quant_params(x)
    q = ref.quantize(x)
    edges_lo = lo + jnp.arange(ref.NUM_BUCKETS) * width
    # each codebook entry lies within (or at the edge of) its bucket:
    # bucket means for non-empty buckets, midpoints for empty ones.
    # clipped values can drag edge-bucket means outside -> allow the
    # clip overflow there.
    inner = slice(1, ref.NUM_BUCKETS - 1)
    cb = q.codebook[inner]
    assert bool(jnp.all(cb >= edges_lo[inner] - 1e-5))
    assert bool(jnp.all(cb <= edges_lo[inner] + width + 1e-5))


@settings(deadline=None, max_examples=20)
@given(st.integers(2, 300), st.integers(0, 2**31 - 1))
def test_quantize_is_deterministic_and_uint8(n, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=n), jnp.float32)
    q1, q2 = ref.quantize(x), ref.quantize(x)
    assert q1.codes.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(q1.codes),
                                  np.asarray(q2.codes))


def test_constant_tensor_roundtrips_exactly():
    x = jnp.full((100,), 3.25, jnp.float32)
    q = ref.quantize(x)
    np.testing.assert_allclose(np.asarray(ref.dequantize(q)),
                               np.asarray(x), atol=1e-6)


def test_wire_bytes_accounting():
    x = jnp.zeros((1000,), jnp.float32)
    q = ref.quantize(x)
    assert q.wire_bytes == 1000 + 4 * 256  # 1 B/elem + codebook
