"""Compile the serving path's Pallas kernels for a TPU v5e at qwen3-0.6b
widths (16 query heads, 8 KV heads, head_dim 128, 16-token chunks, a
4096-token prefix, 512-token cache blocks).

Nothing runs: the TPU compiler shipped with jaxlib compiles for a described
``v5e:2x2`` topology, so Mosaic refuses here whatever it would refuse on the
chip — unaligned blocks, reshapes it cannot lay out, dtypes it cannot load,
more VMEM than a kernel may use.  Interpret mode (every other kernel test)
checks none of that.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and a module that
touched it while being collected would give parallel test workers
different tests.  Keep every such compile in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_quant)
from repro.kernels.flash_attention import flash_attention_quant
from repro.kernels.kv_dequant import kv_dequant, kv_dequant_packed4

H, KV, DH = 16, 8, 128  # qwen3-0.6b attention widths
G = 16                  # chunk tokens
P = 4096                # packed prefix tokens
BLOCK_S = 512
SUFFIX = 200            # a ragged suffix: not a multiple of any block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _packed(bits, group, tokens):
    dhp = DH // 2 if bits == 4 else DH
    qdt = jnp.uint8 if bits == 4 else jnp.int8
    return [((1, tokens, KV, dhp), qdt), ((1, tokens, KV, dhp), qdt),
            ((1, tokens // G, KV * DH // group), jnp.float16),
            ((1, tokens // G, KV * DH // group), jnp.float16)]


@pytest.mark.parametrize("bits,group", [(8, 1), (8, 32), (4, 1), (4, 32)])
@pytest.mark.parametrize("batch", [1, 4])
def test_decode_attention_quant(one_chip, bits, group, batch):
    fn = functools.partial(decode_attention_quant, bits=bits, group=group,
                           chunk_tokens=G, block_s=BLOCK_S,
                           return_residuals=True)
    shapes = _packed(bits, group, P)
    shapes = [((batch,) + s[1:], d) for s, d in shapes]
    _compile(one_chip, fn, ((batch, H, DH), jnp.bfloat16), *shapes[:2],
             *shapes[2:], ((batch,), jnp.int32))


@pytest.mark.parametrize("bits,group", [(8, 1), (8, 32), (4, 1), (4, 32)])
def test_flash_attention_quant_ragged_suffix(one_chip, bits, group):
    fn = functools.partial(flash_attention_quant, bits=bits, group=group,
                           chunk_tokens=G, causal=False, block_q=128,
                           block_k=BLOCK_S, return_residuals=True)
    _compile(one_chip, fn, ((1, SUFFIX, H, DH), jnp.bfloat16),
             *_packed(bits, group, P))


@pytest.mark.parametrize("group", [1, 32])
@pytest.mark.parametrize("bits", [8, 4])
def test_kv_dequant(one_chip, bits, group):
    W = KV * DH
    if bits == 4:
        fn = functools.partial(kv_dequant_packed4, group=group,
                               out_dtype=jnp.bfloat16)
        q = ((P // G, G, W // 2), jnp.uint8)
    else:
        fn = functools.partial(kv_dequant, group=group,
                               out_dtype=jnp.bfloat16)
        q = ((P // G, G, W), jnp.int8)
    _compile(one_chip, fn, q, ((P // G, W // group), jnp.float16))


@pytest.mark.parametrize("batch,S", [(1, P), (4, P + G)])
def test_decode_attention_fp(one_chip, batch, S):
    fn = functools.partial(decode_attention, block_s=BLOCK_S)
    cache = ((batch, S, KV, DH), jnp.bfloat16)
    _compile(one_chip, fn, ((batch, H, DH), jnp.bfloat16), cache, cache,
             ((batch,), jnp.int32))
