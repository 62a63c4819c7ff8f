"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what the chip's compiler
would refuse (block tiling, unsupported primitives, VMEM overuse).
Every test asserts the compiled program holds the kernel as a
``tpu_custom_call`` — in interpret mode it would be plain HLO instead.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file. Compiles are shapes only; nothing is allocated.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import CONFIGS
from repro.core import ring_reduce as rr
from repro.kernels import flash_decode, int8_quant
from repro.models.registry import get_model
from repro.serving.engine import make_engine

# intellect-1 FFN width: 58.7 M elements, past float32's exact 2^24
CHUNK = (4096, 14336)
# internlm2-1.8b decode: 8 slots x 4096 cells, 16 q heads over 8 KV heads
B, S, HQ, HK, DH = 8, 4096, 16, 8, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape factory on one described chip, with the persistent
    compilation cache off (a TPU entry written here could not be read
    back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fused", [False, True])
def test_quant_range_compiles(one_chip, fused):
    x = one_chip(CHUNK, jnp.float32)
    if fused:
        _compile_has_kernel(
            lambda a, t, s: int8_quant.pseudograd_quant_range(
                a, t, scale=s, interpret=False),
            x, x, one_chip((), jnp.float32))
    else:
        _compile_has_kernel(
            lambda x: int8_quant.quant_range(x, interpret=False), x)


def test_encode_hist_compiles(one_chip):
    _compile_has_kernel(
        lambda x, lo, w: int8_quant.encode_hist(x, lo, w, interpret=False),
        one_chip(CHUNK, jnp.float32), one_chip((), jnp.float32),
        one_chip((), jnp.float32))


def test_pseudograd_encode_hist_compiles(one_chip):
    _compile_has_kernel(
        lambda a, t, lo, w, s: int8_quant.pseudograd_encode_hist(
            a, t, lo, w, scale=s, interpret=False),
        one_chip(CHUNK, jnp.float32), one_chip(CHUNK, jnp.float32),
        one_chip((), jnp.float32), one_chip((), jnp.float32),
        one_chip((), jnp.float32))


def test_decode_compiles(one_chip):
    _compile_has_kernel(
        lambda c, b: int8_quant.decode(c, b, interpret=False),
        one_chip(CHUNK, jnp.uint8), one_chip((256,), jnp.float32))


def test_decode_add_compiles(one_chip):
    _compile_has_kernel(
        lambda c, b, a: int8_quant.decode_add(c, b, a, interpret=False),
        one_chip(CHUNK, jnp.uint8), one_chip((256,), jnp.float32),
        one_chip(CHUNK, jnp.float32))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Make the kernels' platform default 'compiled' for code that does
    not pass ``interpret`` itself. Trace caches are cleared on both
    sides so no interpret-mode trace is reused here and no compiled-mode
    trace leaks into later CPU tests."""
    monkeypatch.setattr(int8_quant, "_interpret_default", lambda: False)
    monkeypatch.setattr(flash_decode, "_interpret_default", lambda: False)
    jax.clear_caches()
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("fused", [False, True])
def test_vmapped_ring_hop_compiles(one_chip, compiled_kernels, fused):
    """One reduce-scatter hop over k stacked worker rows, as RingSyncOp
    dispatches it (``_hop_jit`` 'rs' / 'rs_fused'), at mamba2-130m's
    flat size over 2 workers: 64.5 M-element ring chunks."""
    k, size = 2, 129_057_216
    cfg = rr.RingConfig(quant="int8", impl="pallas")
    chunk = -(-size // k)
    accs = one_chip((k, k * chunk), jnp.float32)
    s = one_chip((), jnp.int32)
    if fused:
        _compile_has_kernel(
            lambda s, a, af, tp, wp: rr._rs_hop_rows(
                s, a, k, chunk, chunk, 1, cfg, (af, tp, wp)),
            s, accs, one_chip((k * chunk,), jnp.float32),
            one_chip((k, k * chunk), jnp.float32),
            one_chip((k,), jnp.float32))
    else:
        _compile_has_kernel(
            lambda s, a: rr._rs_hop_rows(s, a, k, chunk, chunk, 1, cfg),
            s, accs)


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_decode_compiles(one_chip, window):
    _compile_has_kernel(
        lambda q, k, v, ln: flash_decode.flash_decode(
            q, k, v, ln, window=window, interpret=False),
        one_chip((B, 1, HQ, DH), jnp.bfloat16),
        one_chip((B, S, HK, DH), jnp.bfloat16),
        one_chip((B, S, HK, DH), jnp.bfloat16),
        one_chip((B,), jnp.int32))


def test_flash_decode_paged_compiles(one_chip):
    blk = 16
    pool = one_chip((B * S // blk, blk, HK, DH), jnp.bfloat16)
    _compile_has_kernel(
        lambda q, k, v, t, ln: flash_decode.flash_decode_paged(
            q, k, v, t, ln, interpret=False),
        one_chip((B, 1, HQ, DH), jnp.bfloat16), pool, pool,
        one_chip((B, S // blk), jnp.int32), one_chip((B,), jnp.int32))


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_decode_chunk_writes_cache_in_place(one_chip, compiled_kernels,
                                            kind):
    """The engine's decode chunk, compiled for the chip, updates the
    stacked KV cache in place: the donated cache aliases the output, and
    no instruction of the stack's shape copies it or rebuilds it with a
    dynamic-update-slice (the new rows go in by scatter)."""
    cfg = dataclasses.replace(CONFIGS["internlm2-1.8b"].reduced(),
                              head_dim=DH, dtype="bfloat16",
                              decode_attn_impl="pallas")
    model = get_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    eng = make_engine(kind, model, None, batch_slots=4, max_len=256,
                      decode_chunk=4)
    state = (eng.cache, eng.tokens, eng.done, eng.remaining, eng.temps,
             eng.slot_keys)
    shapes = jax.tree.map(lambda x: one_chip(x.shape, x.dtype),
                          (params,) + state)
    compiled = eng._chunk_jit.lower(*shapes, n=4).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    stack = ",".join(map(str, eng.cache["scan"].k.shape))
    ops = re.findall(r"%(\S+) = \w+\[" + stack + r"\]", text)
    assert any("scatter" in op for op in ops), ops
    assert not [op for op in ops if "copy" in op
                or re.search("dynamic.update.slice", op)], ops
