"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def ref_flash_attention(q, k, v, *, causal: bool = True) -> jnp.ndarray:
    """q: [B, H, Sq, dh]; k/v: [B, KV, Sk, dh] (GQA: H % KV == 0)."""
    B, H, Sq, dh = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def ref_decode_attention(q, k_cache, v_cache, lengths) -> jnp.ndarray:
    """q: [B, H, dh]; caches: [B, S, KV, dh]; lengths: [B] valid entries."""
    B, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    k = jnp.repeat(k_cache, rep, axis=2)  # [B, S, H, dh]
    v = jnp.repeat(v_cache, rep, axis=2)
    logits = jnp.einsum("bhd,bshd->bhs", q, k.astype(q.dtype),
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", probs.astype(v.dtype), v.astype(q.dtype))


def ref_kv_dequant(q, scales) -> jnp.ndarray:
    """q: [N, R, W] int8; scales: [N, W] fp16 → [N, R, W] f32 — the fused
    dequant oracle (see also the numpy twin `codec.ref.dequantize_per_channel`,
    which the serving client's host path decodes with)."""
    return q.astype(jnp.float32) * scales.astype(jnp.float32)[:, None, :]


def ref_kv_dequant_packed4(q_packed, scales) -> jnp.ndarray:
    """q_packed: [N, R, W/2] uint8 biased-nibble int4 pairs → [N, R, W] f32."""
    lo = (q_packed & 0xF).astype(jnp.int32) - 8
    hi = (q_packed >> 4).astype(jnp.int32) - 8
    N, R, Wh = q_packed.shape
    q = jnp.stack([lo, hi], axis=-1).reshape(N, R, 2 * Wh)
    return q.astype(jnp.float32) * scales.astype(jnp.float32)[:, None, :]


def ref_dequant_cache(q, scales, *, bits: int, group: int,
                      chunk_tokens: int) -> jnp.ndarray:
    """Expand a packed-resident cache to fp32: q [B, S, KV, dh'] (int8, or
    uint8 nibble pairs with dh' = dh/2 when ``bits == 4``) against per-chunk
    scale rows [B, S/G, W/group] fp16 → [B, S, KV, dh].

    Pure jnp and jittable — this is both the fused-attention oracle's dequant
    half and the one expansion of a packed prefix to model width, where it
    enters the batcher's fp cache (`serving.kv_chunks.packed_layer_to_fp`)."""
    B, S, KV = q.shape[0], q.shape[1], q.shape[2]
    if bits == 4:
        lo = (q & 0xF).astype(jnp.int32) - 8
        hi = (q >> 4).astype(jnp.int32) - 8
        q = jnp.stack([lo, hi], axis=-1).reshape(B, S, KV, 2 * q.shape[3])
    q = q.astype(jnp.float32)
    dh = q.shape[3]
    W = KV * dh
    G = chunk_tokens
    NC = S // G
    sw = jnp.repeat(scales.astype(jnp.float32), group, axis=-1)  # [B,NC,W]
    out = q.reshape(B, NC, G, W) * sw[:, :, None, :]
    return out.reshape(B, S, KV, dh)


def ref_decode_attention_quant(q, k_q, v_q, k_scales, v_scales, lengths, *,
                               bits: int, group: int,
                               chunk_tokens: int) -> jnp.ndarray:
    """Composed oracle for `decode_attention_quant`: dequantize the packed
    cache (codec.ref semantics), then the plain decode oracle."""
    k = ref_dequant_cache(k_q, k_scales, bits=bits, group=group,
                          chunk_tokens=chunk_tokens)
    v = ref_dequant_cache(v_q, v_scales, bits=bits, group=group,
                          chunk_tokens=chunk_tokens)
    return ref_decode_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                                lengths)


def ref_flash_attention_quant(q, k_q, v_q, k_scales, v_scales, *, bits: int,
                              group: int, chunk_tokens: int,
                              causal: bool = True,
                              q_offset: int = 0) -> jnp.ndarray:
    """Composed oracle for `flash_attention_quant` (engine-native
    [B, Sq, H, dh] query layout; see that kernel for the ``q_offset``
    causal-mask convention)."""
    B, Sq, H, dh = q.shape
    k = ref_dequant_cache(k_q, k_scales, bits=bits, group=group,
                          chunk_tokens=chunk_tokens)
    v = ref_dequant_cache(v_q, v_scales, bits=bits, group=group,
                          chunk_tokens=chunk_tokens)
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)  # [B, Sk, H, dh]
    v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bshd->bqhs", q.astype(jnp.float32), k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    if causal:
        rows = q_offset + jnp.arange(Sq)[:, None]
        cols = jnp.arange(Sk)[None, :]
        logits = jnp.where((rows >= cols)[None, :, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqhs,bshd->bqhd", probs, v).astype(q.dtype)


def ref_kv_gather(pool, indices) -> jnp.ndarray:
    """pool: [P, G, W]; indices: [N] -> out [N, G, W].

    The ObjectCache server-side aggregation readout: layer-l slices of N
    matched chunks, concatenated in prefix order (Table A3) — on device the
    pool is the paged HBM chunk arena and this is the layer-major assembly."""
    return pool[indices]
