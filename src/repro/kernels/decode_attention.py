"""Pallas TPU decode attention: one query token per sequence against a long
KV cache — the memory-bound hot spot of the decode_32k / long_500k shapes.

Tiling: grid (B, KV lane blocks, ceil(S/bs)) with the cache-scan axis
sequential.  The cache is viewed as [B, S, KV*dh] (a free reshape) and each
grid step streams one [bs, dh] tile of one KV head — a 2-D block whose lane
width is a whole head, which is what the TPU's (8, 128) tiling accepts —
against that head's GQA group of queries ([rep, dh], rep = H/KV), so every
cache byte crosses HBM→VMEM exactly once.  ``lengths`` masks the valid
prefix (pos+1), so one compiled kernel serves every fill level; the same
mask covers the ragged trailing block when S is not a block multiple (the
grid is a ceil-div, padded tail columns sit at ``cols >= S > length``).

`decode_attention_quant` is the fused quantized-cache variant (DESIGN.md
§Kernels): the K/V block specs carry *packed* int8 / nibble-packed int4
tiles plus per-chunk fp16 scale rows (as uint16 bits), and
`kv_dequant.dequant_tile` expands them to fp32 inside the same streaming
inner loop — one HBM pass at wire width instead of a standalone dequant pass
writing model-width KV back to HBM.  A packed int4 lane block holds two
heads (2 × dh/2 bytes = one 128-lane row at dh = 128).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_dequant import dequant_tile, int4_perm, int4_unperm, scale_bits

NEG_INF = float("-inf")


def online_update(q, k, v, mask, sm_scale, m_scr, l_scr, acc_scr, h: int):
    """One online-softmax step for head slot ``h``: q [R, dh] against a k/v
    tile [bs, dh] (fp32), keeping ``mask`` [R, bs].  Shared by every
    attention kernel here; the only difference between them is how the tile
    got into VMEM.  Rows of ``v`` outside the valid range must already be
    *selected* to zero: a ragged block reads past the array (interpret mode
    pads with NaN, a real TPU with garbage), and 0 * NaN = NaN."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[h] = m_new


def lane_block(KV: int, dh: int, bits: int | None) -> tuple[int, int]:
    """(heads per grid step, lane width of one step's cache block) for a
    [.., KV*dh'] cache row.  Int4 packs two heads per block when it can, so
    a dh = 128 model streams full 128-lane rows."""
    if bits == 4:
        return (2, dh) if KV % 2 == 0 else (1, dh // 2)
    return 1, dh


def scale_block(KV: int, dh: int, group: int, hpb: int) -> tuple[int, bool]:
    """Lane width of one step's scale block, and whether it is narrowed to
    this step's heads.  A narrow block must fill whole 128-lane rows; a
    narrower head slice (e.g. 4 groups of 32 channels) reads the full row
    and `dequant_tile` selects its columns."""
    cw = hpb * dh // group  # 0 when one scale group spans several heads
    if cw and (cw % 128 == 0 or hpb == KV):
        return cw, True
    return KV * dh // group, False


def quant_block_s(S: int, chunk_tokens: int, block_s: int) -> int:
    """Largest usable cache block <= ``block_s``: the per-chunk scale rows
    pin the block to either a whole number of chunks or a divisor of one
    chunk, so scale tiles index with plain blocked arithmetic."""
    G = chunk_tokens
    block_s = min(block_s, S)
    if block_s % G == 0 or G % block_s == 0:
        return block_s
    return max(G, (block_s // G) * G)


def _kernel(len_ref, q_ref, k_ref, v_ref, *refs, sm_scale: float,
            block_s: int, num_s: int, hpb: int, dh: int, bits, group: int,
            narrow_cw, residuals: bool):
    if bits is not None:
        ks_ref, vs_ref, *refs = refs
    if residuals:
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    lb = pl.program_id(1)
    js = pl.program_id(2)

    @pl.when(js == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    s_start = js * block_s

    @pl.when(s_start < length)
    def _compute():
        if bits is None:  # one head per step (`lane_block`)
            ks = [k_ref[...].astype(jnp.float32)]
            vs = [v_ref[...].astype(jnp.float32)]
        else:
            # the only HBM bytes this tile moved are wire-width: packed ints
            # + per-chunk scale rows; the fp32 expansion lives in VMEM only
            col0 = lb * narrow_cw if narrow_cw else 0
            args = dict(bits=bits, group=group, dh=dh, head0=lb * hpb,
                        col0=col0)
            ks = dequant_tile(k_ref[...], ks_ref[...], **args)
            vs = dequant_tile(v_ref[...], vs_ref[...], **args)
        rep = q_ref.shape[1]
        cols = s_start + jax.lax.broadcasted_iota(jnp.int32, (rep, block_s), 1)
        rows = s_start + jax.lax.broadcasted_iota(jnp.int32, (block_s, dh), 0)
        for h in range(hpb):
            online_update(q_ref[h].astype(jnp.float32), ks[h],
                          jnp.where(rows < length, vs[h], 0.0),
                          cols < length, sm_scale, m_scr, l_scr, acc_scr, h)

    @pl.when(js == num_s - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if residuals:
            m_ref[...] = m_scr[...]
            l_ref[...] = l


def _decode_call(q, k, v, scales, lengths, *, bits, group: int,
                 chunk_tokens: int, block_s: int, residuals: bool,
                 interpret: bool):
    B, H, dh = q.shape
    S, KV, dhp = k.shape[1], k.shape[2], k.shape[3]
    assert H % KV == 0
    rep = H // KV
    hpb, wq = lane_block(KV, dh, bits)
    nlb = KV // hpb
    # ceil-div grid: a cache whose padded length is not a block multiple gets
    # a ragged trailing block; its padded columns carry cols >= S >= length,
    # so the lengths mask already excludes them.
    num_s = -(-S // block_s)
    kernel_q = q.reshape(B, KV, rep, dh)
    if bits == 4:
        kernel_q = int4_perm(kernel_q)
    cache_spec = pl.BlockSpec((None, block_s, wq),
                              lambda b, lb, js, len_ref: (b, js, lb))
    in_specs = [pl.BlockSpec((None, hpb, rep, dh),
                             lambda b, lb, js, len_ref: (b, lb, 0, 0)),
                cache_spec, cache_spec]
    operands = [lengths, kernel_q, k.reshape(B, S, KV * dhp),
                v.reshape(B, S, KV * dhp)]
    narrow_cw = None
    if bits is not None:
        G = chunk_tokens
        ncb = max(1, block_s // G)  # scale rows riding each tile
        stride = max(1, G // block_s)  # cache blocks per chunk if G > bs
        cw, narrow = scale_block(KV, dh, group, hpb)
        narrow_cw = cw if narrow else None

        def scale_idx(b, lb, js, len_ref):
            del len_ref
            return (b, js // stride, lb if narrow else 0)
        in_specs += [pl.BlockSpec((None, ncb, cw), scale_idx)] * 2
        operands += [scale_bits(s) for s in scales]
    head_spec = pl.BlockSpec((None, hpb, rep, dh),
                             lambda b, lb, js, len_ref: (b, lb, 0, 0))
    row_spec = pl.BlockSpec((None, hpb, rep, 1),
                            lambda b, lb, js, len_ref: (b, lb, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((B, KV, rep, dh), q.dtype)]
    out_specs = [head_spec]
    if residuals:
        out_shape += [jax.ShapeDtypeStruct((B, KV, rep, 1), jnp.float32)] * 2
        out_specs += [row_spec] * 2
    kernel = functools.partial(
        _kernel, sm_scale=1.0 / math.sqrt(dh), block_s=block_s, num_s=num_s,
        hpb=hpb, dh=dh, bits=bits, group=group, narrow_cw=narrow_cw,
        residuals=residuals)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths land in SMEM before the grid runs
        grid=(B, nlb, num_s),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hpb, rep, 1), jnp.float32),
            pltpu.VMEM((hpb, rep, 1), jnp.float32),
            pltpu.VMEM((hpb, rep, dh), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    out = outs[0]
    if bits == 4:
        out = int4_unperm(out)
    out = out.reshape(B, H, dh)
    if not residuals:
        return out
    return out, outs[1].reshape(B, H), outs[2].reshape(B, H)


def decode_attention(q, k_cache, v_cache, lengths, *, block_s: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, dh]; caches: [B, S, KV, dh]; lengths: [B] -> [B, H, dh]."""
    block_s = min(block_s, k_cache.shape[1])
    return _decode_call(q, k_cache, v_cache, None, lengths, bits=None,
                        group=1, chunk_tokens=1, block_s=block_s,
                        residuals=False, interpret=interpret)


def decode_attention_quant(q, k_q, v_q, k_scales, v_scales, lengths, *,
                           bits: int, group: int, chunk_tokens: int,
                           block_s: int = 512, return_residuals: bool = False,
                           interpret: bool = False):
    """Fused dequant + decode attention over a packed-resident cache.

    q: [B, H, dh]; k_q/v_q: [B, S, KV, dh'] (int8, or uint8 nibble pairs with
    dh' = dh/2 when ``bits == 4``); k_scales/v_scales: [B, S/G, W/group] fp16
    per-chunk scale rows (W = KV*dh, G = ``chunk_tokens``); lengths: [B].

    Returns [B, H, dh], or (out, m [B, H], l [B, H]) softmax residuals with
    ``return_residuals`` so callers can merge against a disjoint key set
    (the serving engines' fp-resident suffix segment).
    """
    B, H, dh = q.shape
    S, KV, dhp = k_q.shape[1], k_q.shape[2], k_q.shape[3]
    assert dh == (2 * dhp if bits == 4 else dhp), (dh, dhp, bits)
    G = chunk_tokens
    assert S % G == 0, (S, G)
    ng = (KV * dh) // group
    assert k_scales.shape == (B, S // G, ng), (k_scales.shape, (B, S // G, ng))
    assert v_scales.shape == (B, S // G, ng)
    return _decode_call(q, k_q, v_q, (k_scales, v_scales), lengths, bits=bits,
                        group=group, chunk_tokens=G,
                        block_s=quant_block_s(S, G, block_s),
                        residuals=return_residuals, interpret=interpret)
