"""Pallas TPU flash attention (causal / full, GQA), the prefill hot spot.

Tiling: grid (B, H, Sq/bq, Sk/bk); the last axis is sequential ("arbitrary")
so the online-softmax running state (m, l, acc) lives in VMEM scratch across
kv blocks.  Block sizes default to 128 — MXU-aligned (128x128 systolic) and
small enough that q/k/v/acc tiles fit VMEM:
    bq*dh + 2*bk*dh + bq*bk + bq*dh(acc)  ~  128*128*4 floats * few  « 16 MiB.
GQA is folded into the k/v index_map (head h reads kv head h // (H//KV)), so
no repeated-KV materialisation ever hits HBM.

`flash_attention_quant` is the fused quantized-cache prefill variant
(DESIGN.md §Kernels): K/V block specs carry packed int8 / nibble-packed int4
tiles plus per-chunk fp16 scale rows, expanded to fp32 by
`kv_dequant.dequant_tile` inside the streaming kv loop.  It takes the
serving engines' native [B, S, heads, dh] layout and regroups the queries
outside the kernel so that, like the plain kernel, it puts the KV head on
the grid: each step attends one [bk, dh] cache tile of one KV head (two for
packed int4) with the [bq*rep, dh] rows of its GQA group, all 2-D.  It can
return the (m, l) softmax residuals so a caller can merge its output with
attention over a disjoint key set — the engines' fp-resident suffix
segment.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (NEG_INF, lane_block, online_update,
                               quant_block_s, scale_block)
from .kv_dequant import dequant_tile, int4_perm, int4_unperm, scale_bits


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, sm_scale: float, block_q: int, block_k: int,
            num_kq: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # Causal: skip kv blocks strictly above the diagonal.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale  # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (m == -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == num_kq - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, Sq, dh]; k/v: [B, KV, Sk, dh] -> [B, H, Sq, dh]."""
    B, H, Sq, dh = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0 and Sq % block_q == 0 and Sk % block_k == 0
    group = H // KV
    nq, nk = Sq // block_q, Sk // block_k
    sm_scale = 1.0 / math.sqrt(dh)

    grid = (B, H, nq, nk)
    kernel = functools.partial(_kernel, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k, num_kq=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# fused quantized-cache variant
# ---------------------------------------------------------------------------
def _quant_kernel(q_ref, kq_ref, vq_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref,
                  m_scr, l_scr, acc_scr, *, causal: bool, sm_scale: float,
                  block_q: int, block_k: int, num_k: int, q_offset: int,
                  Sk: int, rep: int, hpb: int, dh: int, bits: int,
                  group: int, narrow_cw):
    lb = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ik * block_k
    run = True
    if causal:
        run = k_start <= q_offset + iq * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        col0 = lb * narrow_cw if narrow_cw else 0
        args = dict(bits=bits, group=group, dh=dh, head0=lb * hpb, col0=col0)
        ks = dequant_tile(kq_ref[...], ks_ref[...], **args)
        vs = dequant_tile(vq_ref[...], vs_ref[...], **args)
        R = q_ref.shape[1]  # block_q query positions x rep heads, s-major
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (R, block_k), 1)
        mask = cols < Sk  # ragged trailing kv block
        if causal:
            # row r is query position q_offset + r // rep, and
            # r // rep >= n  <=>  r >= n * rep for integer n
            rows = iq * R + jax.lax.broadcasted_iota(
                jnp.int32, (R, block_k), 0)
            mask = mask & (rows >= (cols - q_offset) * rep)
        krows = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_k, dh), 0)
        for h in range(hpb):
            online_update(q_ref[h].astype(jnp.float32), ks[h],
                          jnp.where(krows < Sk, vs[h], 0.0), mask, sm_scale,
                          m_scr, l_scr, acc_scr, h)

    @pl.when(ik == num_k - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        m_ref[...] = m_scr[...]
        l_ref[...] = l


def flash_attention_quant(q, k_q, v_q, k_scales, v_scales, *,
                          bits: int, group: int, chunk_tokens: int,
                          causal: bool = True, q_offset: int = 0,
                          block_q: int = 128, block_k: int = 512,
                          return_residuals: bool = False,
                          interpret: bool = False):
    """Fused dequant + flash attention over a packed-resident prefix.

    q: [B, Sq, H, dh] (engine-native layout); k_q/v_q: [B, Sk, KV, dh']
    (int8, or uint8 nibble pairs with dh' = dh/2 when ``bits == 4``);
    k_scales/v_scales: [B, Sk/G, W/group] fp16 per-chunk scale rows
    (W = KV*dh, G = ``chunk_tokens``).  ``q_offset`` places query row 0 at
    absolute position ``q_offset`` for the causal mask (suffix queries over a
    prefix cache).  Returns [B, Sq, H, dh], or (out, m [B, Sq, H],
    l [B, Sq, H]) with ``return_residuals``.

    Any Sq works: the queries are padded to a whole number of ``block_q``
    blocks outside the kernel (so VMEM use is bounded by the block, never
    by the suffix length) and the padding is sliced off the outputs.
    """
    B, Sq, H, dh = q.shape
    Sk, KV, dhp = k_q.shape[1], k_q.shape[2], k_q.shape[3]
    assert dh == (2 * dhp if bits == 4 else dhp), (dh, dhp, bits)
    assert H % KV == 0
    rep = H // KV
    G = chunk_tokens
    assert Sk % G == 0, (Sk, G)
    ng = (KV * dh) // group
    assert k_scales.shape == (B, Sk // G, ng), (k_scales.shape,
                                                (B, Sk // G, ng))
    assert v_scales.shape == (B, Sk // G, ng)
    block_q = min(block_q, -(-Sq // 8) * 8)
    Sqp = -(-Sq // block_q) * block_q
    nq = Sqp // block_q
    block_k = quant_block_s(Sk, G, block_k)
    nk = -(-Sk // block_k)  # ragged tail masked in-kernel
    ncb = max(1, block_k // G)
    stride = max(1, G // block_k)
    hpb, wq = lane_block(KV, dh, bits)
    cw, narrow = scale_block(KV, dh, group, hpb)
    R = block_q * rep

    # [B, Sq, H, dh] -> [B, KV, Sqp*rep, dh]: each KV head's query rows,
    # position-major, so a block of rows is a block of positions
    qk = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0), (0, 0)))
    qk = qk.reshape(B, Sqp, KV, rep, dh).transpose(0, 2, 1, 3, 4)
    qk = qk.reshape(B, KV, Sqp * rep, dh)
    if bits == 4:
        qk = int4_perm(qk)

    kernel = functools.partial(
        _quant_kernel, causal=causal, sm_scale=1.0 / math.sqrt(dh),
        block_q=block_q, block_k=block_k, num_k=nk, q_offset=q_offset, Sk=Sk,
        rep=rep, hpb=hpb, dh=dh, bits=bits, group=group,
        narrow_cw=cw if narrow else None)

    def row_idx(b, lb, iq, ik):
        return (b, lb, iq, 0)

    def cache_idx(b, lb, iq, ik):
        return (b, ik, lb)

    def scale_idx(b, lb, iq, ik):
        return (b, ik // stride, lb if narrow else 0)

    out, m, l = pl.pallas_call(
        kernel,
        grid=(B, KV // hpb, nq, nk),
        in_specs=[
            pl.BlockSpec((None, hpb, R, dh), row_idx),
            pl.BlockSpec((None, block_k, wq), cache_idx),
            pl.BlockSpec((None, block_k, wq), cache_idx),
            pl.BlockSpec((None, ncb, cw), scale_idx),
            pl.BlockSpec((None, ncb, cw), scale_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, hpb, R, dh), row_idx),
            pl.BlockSpec((None, hpb, R, 1), row_idx),
            pl.BlockSpec((None, hpb, R, 1), row_idx),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Sqp * rep, dh), q.dtype),
            jax.ShapeDtypeStruct((B, KV, Sqp * rep, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, Sqp * rep, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hpb, R, 1), jnp.float32),
            pltpu.VMEM((hpb, R, 1), jnp.float32),
            pltpu.VMEM((hpb, R, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qk, k_q.reshape(B, Sk, KV * dhp), v_q.reshape(B, Sk, KV * dhp),
      scale_bits(k_scales), scale_bits(v_scales))
    if bits == 4:
        out = int4_unperm(out)
    out = out.reshape(B, KV, Sqp, rep, dh).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, Sqp, H, dh)[:, :Sq]
    if not return_residuals:
        return out

    def rows_to_heads(a):  # [B, KV, Sqp*rep, 1] -> [B, Sq, H]
        a = a.reshape(B, KV, Sqp, rep).transpose(0, 2, 1, 3)
        return a.reshape(B, Sqp, H)[:, :Sq]
    return out, rows_to_heads(m), rows_to_heads(l)
