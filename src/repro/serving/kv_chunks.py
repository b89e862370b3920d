"""Bridging model KV caches <-> wire-encoded chunk objects.

The model side speaks [L, 2, B, S, KV, dh] arrays; the storage side speaks
immutable per-chunk byte objects (layer-major, encoded by ``spec.codec`` —
DESIGN.md §Codec).  These converters are the only place the two layouts meet.

bf16 note: numpy has no native bfloat16, so device bf16 arrays cross the
identity boundary as uint16 words (bit-identical); JAX views them back on the
way in.  Quantized codecs instead receive the *typed* arrays (ml_dtypes
handles bf16 on the host) because quantization needs values, not bits.

Decode paths: the identity codec is a bit view (never a value cast).  On the
device path the quantized codecs dequantize through the fused Pallas
kernels; the host path (`layer_payload_to_kv`) uses the numpy reference
(`codec.ref`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax.numpy as jnp

from repro.codec import get_codec
from repro.core import KVSpec
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import ref_dequant_cache
from repro.models.config import ModelConfig

# Explicit quantized-width -> standalone dequant kernel dispatch.  A lookup
# (rather than `4 -> packed4, anything else -> int8`) means a future 2/6-bit
# layer raises here instead of silently dequantizing garbage through the
# int8 kernel.
_DEQUANT_OPS = {
    8: kernel_ops.kv_dequant_op,
    4: kernel_ops.kv_dequant_packed4_op,
}


def _dequant_op_for(bits: int):
    try:
        return _DEQUANT_OPS[bits]
    except KeyError:
        raise ValueError(
            f"no dequant kernel for {bits}-bit payloads; known widths: "
            f"{sorted(_DEQUANT_OPS)}") from None


def cache_to_chunks(cache, keys: list[bytes], spec: KVSpec, batch_row: int = 0,
                    start_token: int = 0) -> dict[bytes, bytes]:
    """Pack ``len(keys)`` G-token chunks of one sequence's KV into encoded
    objects (``spec.codec``).

    ``cache``: [L, 2, B, S, KV, dh] (prefix+suffix as produced by prefill).
    Chunk i covers tokens [start_token + i*G, start_token + (i+1)*G).
    """
    G = spec.chunk_tokens
    L = spec.num_layers
    width = spec.width
    codec = get_codec(spec.codec)
    arr = np.asarray(cache)  # typed (ml_dtypes for bf16); codec picks its view
    out: dict[bytes, bytes] = {}
    for i, key in enumerate(keys):
        lo = start_token + i * G
        sl = arr[:, :, batch_row, lo:lo + G]  # [L, 2, G, KV, dh]
        k = np.ascontiguousarray(sl[:, 0].reshape(L, G, width))
        v = np.ascontiguousarray(sl[:, 1].reshape(L, G, width))
        out[key] = codec.encode_chunk(k, v, spec)
    return out


def layer_payload_to_kv(payload: bytes, num_chunks: int, spec: KVSpec, dtype,
                        layer: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """One aggregated layer payload -> (k, v) [P, KV, dh] arrays (P = N*G).

    Host-side decode: identity is a bit view; quantized codecs dequantize via
    the numpy reference.  ``layer`` selects the per-layer parameters of a
    variable-rate codec (mixed-bit); uniform codecs ignore it."""
    codec = get_codec(spec.codec)
    k, v = codec.decode_layer_payload(payload, num_chunks, spec,
                                      np.dtype(jnp.dtype(dtype)), layer=layer)
    P = num_chunks * spec.chunk_tokens
    shape = (P, spec.num_kv_heads, spec.head_dim)
    return k.reshape(shape), v.reshape(shape)


def layer_payload_to_device_kv(payload: bytes, num_chunks: int, spec: KVSpec,
                               dtype, layer: int = 0
                               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side decode of one aggregated layer payload -> (k, v) jnp
    [P, KV, dh].

    For quantized codecs this uploads the *compressed* tensors (int8/packed
    int4 + fp16 scales, possibly group-wise) and runs the fused Pallas
    dequant kernel, so the host->device copy moves wire bytes, not decoded
    bytes.  The identity codec is a bit view."""
    codec = get_codec(spec.codec)
    G = spec.chunk_tokens
    P = num_chunks * G
    shape = (P, spec.num_kv_heads, spec.head_dim)
    if codec.lossless:
        k, v = layer_payload_to_kv(payload, num_chunks, spec, dtype, layer)
        return jnp.asarray(k), jnp.asarray(v)
    q, scales = codec.parse_layer_payload(payload, num_chunks, spec, layer)
    group = codec.layer_group(spec, layer)
    op = _dequant_op_for(codec.layer_bits(spec, layer))
    kq = np.ascontiguousarray(q[:, :G])
    vq = np.ascontiguousarray(q[:, G:])
    k = op(jnp.asarray(kq), jnp.asarray(np.ascontiguousarray(scales[:, 0, :])),
           group=group, out_dtype=jnp.dtype(dtype))
    v = op(jnp.asarray(vq), jnp.asarray(np.ascontiguousarray(scales[:, 1, :])),
           group=group, out_dtype=jnp.dtype(dtype))
    return k.reshape(shape), v.reshape(shape)


@dataclasses.dataclass(frozen=True)
class PackedLayerKV:
    """One layer's prefix KV kept *quantized-resident* on device.

    The wire image of an aggregated layer payload, uploaded as-is: packed
    integer tensors plus the per-chunk fp16 scale rows, never expanded to
    model width in HBM.  The fused attention kernels
    (`decode_attention_quant` / `flash_attention_quant`) consume exactly
    these arrays; `kernels.ref.ref_dequant_cache` is their jnp reference.
    Leading batch dim is 1 (one sequence's prefix), matching the engines'
    prefix-KV convention."""

    k_q: jnp.ndarray       # [1, P, KV, dh'] int8 (or uint8 nibbles, dh'=dh/2)
    v_q: jnp.ndarray       # [1, P, KV, dh']
    k_scales: jnp.ndarray  # [1, NC, W/group] fp16
    v_scales: jnp.ndarray  # [1, NC, W/group]
    bits: int
    group: int
    chunk_tokens: int

    @property
    def tokens(self) -> int:
        return self.k_q.shape[1]

    @property
    def resident_bytes(self) -> int:
        """HBM bytes this prefix pins (the wire-resident footprint)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (self.k_q, self.v_q, self.k_scales, self.v_scales))

    def as_tuple(self):
        """The jit-friendly array 4-tuple the fused kernel ops take."""
        return (self.k_q, self.v_q, self.k_scales, self.v_scales)


def layer_payload_to_packed_kv(payload: bytes, num_chunks: int, spec: KVSpec,
                               layer: int = 0) -> PackedLayerKV:
    """One aggregated layer payload -> quantized-resident device arrays.

    The quantized-resident counterpart of `layer_payload_to_device_kv`: the
    host->device copy moves wire bytes and *stays* wire-sized — no dequant
    kernel runs; dequantization happens inside the fused attention kernels
    at read time.  Raises for lossless codecs (identity has no packed form)
    and for bit widths without a registered kernel."""
    codec = get_codec(spec.codec)
    if codec.lossless:
        raise ValueError(
            f"codec {spec.codec!r} is lossless; quantized-resident caching "
            f"needs a quantized codec")
    bits = codec.layer_bits(spec, layer)
    _dequant_op_for(bits)  # unknown widths raise before any upload
    group = codec.layer_group(spec, layer)
    G = spec.chunk_tokens
    q, scales = codec.parse_layer_payload(payload, num_chunks, spec, layer)
    dhp = spec.head_dim // 2 if bits == 4 else spec.head_dim
    shape = (1, num_chunks * G, spec.num_kv_heads, dhp)
    kq = np.ascontiguousarray(q[:, :G]).reshape(shape)
    vq = np.ascontiguousarray(q[:, G:]).reshape(shape)
    ks = np.ascontiguousarray(scales[:, 0, :])[None]
    vs = np.ascontiguousarray(scales[:, 1, :])[None]
    return PackedLayerKV(jnp.asarray(kq), jnp.asarray(vq), jnp.asarray(ks),
                         jnp.asarray(vs), bits=bits, group=group,
                         chunk_tokens=G)


def packed_layer_to_fp(pkv: PackedLayerKV, dtype) -> tuple[jnp.ndarray,
                                                           jnp.ndarray]:
    """Expand a packed-resident layer to model-width (k, v) [1, P, KV, dh].

    The materialization boundary: continuous-batching decode pools multiple
    sequences into one fp cache, so a packed prefix entering the batcher is
    expanded exactly once here."""
    k = ref_dequant_cache(pkv.k_q, pkv.k_scales, bits=pkv.bits,
                          group=pkv.group, chunk_tokens=pkv.chunk_tokens)
    v = ref_dequant_cache(pkv.v_q, pkv.v_scales, bits=pkv.bits,
                          group=pkv.group, chunk_tokens=pkv.chunk_tokens)
    return k.astype(dtype), v.astype(dtype)


def prefix_kv_from_payloads(payloads: list[bytes], num_chunks: int,
                            spec: KVSpec, dtype) -> jnp.ndarray:
    """All layers -> [L, 2, 1, P, KV, dh] prefix-KV (batch dim of 1)."""
    ks, vs = [], []
    for layer, payload in enumerate(payloads):
        k, v = layer_payload_to_kv(payload, num_chunks, spec, dtype, layer)
        ks.append(k)
        vs.append(v)
    k = np.stack(ks)[:, None]  # [L, 1, P, KV, dh] -> stack along new axis 1
    v = np.stack(vs)[:, None]
    return jnp.asarray(np.stack([k, v], axis=1))  # [L, 2, 1, P, KV, dh]


def chunks_from_store(store, keys: list[bytes]) -> list[bytes]:
    return [store.get(k) for k in keys]
