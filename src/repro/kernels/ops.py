"""Jitted public wrappers for the Pallas kernels.

The kernels compile to Mosaic on a TPU.  On the CPU backend (tests, CPU-only
installations) they run in interpret mode — the kernel body runs per grid
step, which validates the tiling and semantics.  `_interpret()` is that one
rule; no option or environment variable overrides it, and a kernel that
fails to compile raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention as _decode
from .decode_attention import decode_attention_quant as _decode_quant
from .flash_attention import flash_attention as _flash
from .flash_attention import flash_attention_quant as _flash_quant
from .kv_dequant import kv_dequant as _dequant
from .kv_dequant import kv_dequant_packed4 as _dequant_p4
from .kv_gather import kv_gather as _gather


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_op(q, k, v, *, causal: bool = True, block_q: int = 128,
                       block_k: int = 128):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention_op(q, k_cache, v_cache, lengths, *, block_s: int = 512):
    return _decode(q, k_cache, v_cache, lengths, block_s=block_s,
                   interpret=_interpret())


@functools.partial(jax.jit, static_argnames=(
    "bits", "group", "chunk_tokens", "block_s", "return_residuals"))
def decode_attention_quant_op(q, k_q, v_q, k_scales, v_scales, lengths, *,
                              bits: int, group: int, chunk_tokens: int,
                              block_s: int = 512,
                              return_residuals: bool = False):
    return _decode_quant(q, k_q, v_q, k_scales, v_scales, lengths, bits=bits,
                         group=group, chunk_tokens=chunk_tokens,
                         block_s=block_s, return_residuals=return_residuals,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=(
    "bits", "group", "chunk_tokens", "causal", "q_offset", "block_q",
    "block_k", "return_residuals"))
def flash_attention_quant_op(q, k_q, v_q, k_scales, v_scales, *, bits: int,
                             group: int, chunk_tokens: int,
                             causal: bool = True, q_offset: int = 0,
                             block_q: int = 128, block_k: int = 512,
                             return_residuals: bool = False):
    return _flash_quant(q, k_q, v_q, k_scales, v_scales, bits=bits,
                        group=group, chunk_tokens=chunk_tokens, causal=causal,
                        q_offset=q_offset, block_q=block_q, block_k=block_k,
                        return_residuals=return_residuals,
                        interpret=_interpret())


@jax.jit
def kv_gather_op(pool, indices):
    return _gather(pool, indices, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("group", "out_dtype"))
def kv_dequant_op(q, scales, *, group: int = 1, out_dtype=jnp.float32):
    return _dequant(q, scales, group=group, out_dtype=out_dtype,
                    interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("group", "out_dtype"))
def kv_dequant_packed4_op(q_packed, scales, *, group: int = 1,
                          out_dtype=jnp.float32):
    return _dequant_p4(q_packed, scales, group=group, out_dtype=out_dtype,
                       interpret=_interpret())
