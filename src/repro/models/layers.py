"""Shared neural-net building blocks (functional, dict-pytree params).

Conventions:
  * activations  [B, S, d] in ``compute_dtype`` (bf16), reductions in fp32;
  * attention heads kept as a fused ``H*dh`` dim at the projection boundary
    (always divisible by the mesh 'model' axis) and reshaped inside;
  * every ``init_*`` returns a dict pytree; every apply fn is pure.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig


def dt(cfg: ModelConfig) -> jnp.dtype:
    return jnp.dtype(cfg.compute_dtype)


def pdt(cfg: ModelConfig) -> jnp.dtype:
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def _normal(key, shape, stddev, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)


def init_linear(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    stddev = scale if scale is not None else d_in ** -0.5
    return {"w": _normal(key, (d_in, d_out), stddev, dtype)}


def linear(p, x):
    return x @ p["w"].astype(x.dtype)


def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, dh]; positions: [B, S] or [S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (MHA / GQA / MQA, qk-norm, prefix-KV injection, KV cache decode)
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": init_linear(kq, d, H * dh, pdt(cfg)),
        "wk": init_linear(kk, d, KV * dh, pdt(cfg)),
        "wv": init_linear(kv, d, KV * dh, pdt(cfg)),
        "wo": init_linear(ko, H * dh, d, pdt(cfg), scale=(H * dh) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(dh, pdt(cfg))
        p["k_norm"] = init_rmsnorm(dh, pdt(cfg))
    return p


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def attention_scores(q, k, v, mask, softcap: float = 0.0):
    """q: [B,Sq,H,dh], k/v: [B,Sk,H,dh], mask: broadcastable [B,1,Sq,Sk]."""
    dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def _blocked_attn_one_qblock(qblk, k, v, *, causal, rows, block_k, softcap):
    """Online-softmax over the KV prefix for one q tile.

    The KV loop is a ``layer_scan`` (rematerialised body) so (a) the [Sq,Sk]
    score matrix never materialises and (b) the dry-run cost pass unrolls it
    and counts true FLOPs.
    """
    from .scan_util import layer_scan
    B, bq, H, dh = qblk.shape
    Sk = k.shape[1]
    nb = Sk // block_k
    scale = 1.0 / math.sqrt(dh)
    kb = jnp.moveaxis(k.reshape(B, nb, block_k, H, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block_k, H, dh), 1, 0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kblk, vblk, iblk = xs
        # bf16 inputs, fp32 MXU accumulation — no fp32 operand copies in HBM
        s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        if causal:
            cols = iblk * block_k + jnp.arange(block_k)
            s = jnp.where((cols[None, :] <= rows[:, None])[None, None], s,
                          -jnp.inf)
        m_cur = jnp.max(s, axis=-1)  # [B,H,bq]
        m_new = jnp.maximum(m_prev, m_cur)
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        # p travels to the MXU in bf16 (halves tile traffic); accumulate fp32
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    init = (jnp.full((B, H, bq), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, bq), jnp.float32),
            jnp.zeros((B, H, bq, dh), jnp.float32))
    (m, l, acc), _ = layer_scan(jax.checkpoint(body), init,
                                (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2)  # [B,bq,H,dh] fp32


def attention_scores_blocked(q, k, v, *, causal: bool, q_offset: int,
                             block_k: int = 512, softcap: float = 0.0,
                             num_q_blocks: int = 4):
    """Flash-style blocked attention in plain XLA ops (§Perf optimization O1).

    Two-level tiling: a static Python loop over ``num_q_blocks`` query tiles
    (so each tile attends ONLY to its causal KV prefix — above-diagonal
    blocks are skipped *structurally*, ~2x fewer FLOPs at long Sq), and an
    online-softmax ``layer_scan`` over KV tiles inside (so bytes-accessed is
    O(Sq*block_k) instead of O(Sq*Sk)).  Mirrors the schedule of
    kernels/flash_attention.py, which is the Mosaic version for real TPUs.
    """
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    assert Sk % block_k == 0
    if not causal or Sq % num_q_blocks != 0 or Sq // num_q_blocks < 1:
        rows = q_offset + jnp.arange(Sq)
        out = _blocked_attn_one_qblock(q, k, v, causal=causal, rows=rows,
                                       block_k=block_k, softcap=softcap)
        return out.astype(v.dtype)
    bq = Sq // num_q_blocks
    outs = []
    for i in range(num_q_blocks):
        qblk = q[:, i * bq:(i + 1) * bq]
        rows = q_offset + i * bq + jnp.arange(bq)
        # causal KV horizon of this q tile, rounded up to a whole KV block
        hi = min(Sk, ((q_offset + (i + 1) * bq + block_k - 1)
                      // block_k) * block_k)
        outs.append(_blocked_attn_one_qblock(
            qblk, k[:, :hi], v[:, :hi], causal=True, rows=rows,
            block_k=block_k, softcap=softcap))
    return jnp.concatenate(outs, axis=1).astype(v.dtype)


def project_qkv(p, cfg: ModelConfig, x, kv_x=None):
    """Returns q [B,S,H,dh], k/v [B,S_kv,KV,dh] after qk-norm (pre-RoPE)."""
    B, S, _ = x.shape
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv_in = x if kv_x is None else kv_x
    q = linear(p["wq"], x).reshape(B, S, H, dh)
    k = linear(p["wk"], kv_in).reshape(B, kv_in.shape[1], KV, dh)
    v = linear(p["wv"], kv_in).reshape(B, kv_in.shape[1], KV, dh)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    return q, k, v


def attention(p, cfg: ModelConfig, x, *, positions, causal: bool = True,
              prefix_kv=None, kv_x=None, use_rope: bool = True):
    """Full-sequence attention with optional prefix-KV injection.

    ``prefix_kv``: optional (k, v) each [B, P, KV, dh] — the ObjectCache
    prefix: queries of this (suffix) segment attend over prefix + suffix.
    Returns (out [B,S,d], (k, v) of THIS segment) so callers can build caches
    or commit new chunks.
    """
    B, S, _ = x.shape
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = project_qkv(p, cfg, x, kv_x)
    if cfg.attn_impl == "blocked" and cfg.attn_seq_shard:
        # O2, placed BEFORE RoPE: the fp32 position math must already be
        # Sq-sharded, or GSPMD gathers fp32 full-head tensors per layer
        # (measured: 1294 all-gathers of [B,S,H,dh/2] f32 without this).
        from jax.sharding import PartitionSpec as _P
        q = jax.lax.with_sharding_constraint(q, _P(None, "model", None, None))
        # K/V: batch stays on 'data'; replicated over 'model' only (bf16)
        k = jax.lax.with_sharding_constraint(k, _P("data", None, None, None))
        v = jax.lax.with_sharding_constraint(v, _P("data", None, None, None))
    if use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    seg_kv = (k, v)
    if prefix_kv is not None:
        k = jnp.concatenate([prefix_kv[0].astype(k.dtype), k], axis=1)
        v = jnp.concatenate([prefix_kv[1].astype(v.dtype), v], axis=1)
    Sk = k.shape[1]
    P = Sk - S
    kr, vr = _repeat_kv(k, H // KV), _repeat_kv(v, H // KV)
    if cfg.attn_impl == "blocked" and Sk % cfg.attn_block_k == 0:
        out = attention_scores_blocked(
            q, kr, vr, causal=(causal and kv_x is None), q_offset=P,
            block_k=cfg.attn_block_k, softcap=cfg.logit_softcap)
    else:
        if causal and kv_x is None:
            # absolute key position j visible to suffix-query i when j <= i+P
            iq = jnp.arange(S)[:, None] + P
            jk = jnp.arange(Sk)[None, :]
            mask = (jk <= iq)[None, None, :, :]
        else:
            mask = jnp.ones((1, 1, S, Sk), dtype=bool)
        out = attention_scores(q, kr, vr, mask, cfg.logit_softcap)
    out = linear(p["wo"], out.reshape(B, S, H * dh))
    return out, seg_kv


def attention_partials(q, k, v, mask, softcap: float = 0.0):
    """Softmax attention over one key segment, returning partials.

    q: [B,Sq,H,dh], k/v: [B,Sk,H,dh] (heads already repeated), mask
    broadcastable to [B,1,Sq,Sk].  Returns (o, m, l): the *normalized* fp32
    output [B,Sq,H,dh] plus the running-softmax residuals m/l [B,Sq,H], so
    attention over disjoint key segments (e.g. a packed-resident prefix and
    an fp suffix) composes exactly via `merge_attention_partials` — the same
    (m, l) contract the fused Pallas kernels emit with return_residuals."""
    dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # [B,H,Sq]
    safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(logits), jnp.exp(logits - safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)
    return o, m.swapaxes(1, 2), l.swapaxes(1, 2)  # [B,Sq,H,dh], [B,Sq,H] x2


def merge_attention_partials(parts):
    """Combine per-segment (o, m, l) partials into the exact full softmax.

    Each part: o [..., H, dh] normalized, m/l [..., H] (any matching leading
    shape — prefill [B,Sq,H] and decode [B,H] both work).  Standard
    log-sum-exp merge: with global max m_g, each segment re-weights by
    exp(m - m_g) * l."""
    m_g = parts[0][1]
    for _, m, _ in parts[1:]:
        m_g = jnp.maximum(m_g, m)
    num = 0.0
    denom = 0.0
    for o, m, l in parts:
        w = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g), 0.0) * l
        num = num + w[..., None] * o.astype(jnp.float32)
        denom = denom + w
    return num / jnp.maximum(denom, 1e-30)[..., None]


def attention_packed_prefix(p, cfg: ModelConfig, x, packed_kv, *, positions,
                            bits: int, group: int, chunk_tokens: int):
    """Suffix attention over a *quantized-resident* prefix (prefill form).

    ``packed_kv``: (k_q, v_q, k_scales, v_scales) — the wire image of the
    prefix as `serving.kv_chunks.PackedLayerKV.as_tuple()` yields it (passed
    as a bare tuple so this module never imports the serving layer).  The
    prefix half runs the fused `flash_attention_quant` kernel; the suffix
    half is ordinary causal attention over this segment's own fp KV; the two
    merge exactly via the softmax residuals.  Requires
    ``cfg.logit_softcap == 0`` (the fused kernels don't implement softcap).

    Returns (out [B,S,d], seg_kv) exactly like `attention`.
    """
    from repro.kernels import ops as kernel_ops

    B, S, _ = x.shape
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k_q, v_q, k_scales, v_scales = packed_kv
    q, k, v = project_qkv(p, cfg, x)
    # packed prefixes always carry RoPE'd KV (they were committed post-RoPE)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    seg_kv = (k, v)
    if k_q.shape[0] != B:
        k_q, v_q, k_scales, v_scales = (
            jnp.broadcast_to(a, (B,) + a.shape[1:])
            for a in (k_q, v_q, k_scales, v_scales))
    # every prefix position precedes every suffix query: non-causal
    o_p, m_p, l_p = kernel_ops.flash_attention_quant_op(
        q, k_q, v_q, k_scales, v_scales, bits=bits, group=group,
        chunk_tokens=chunk_tokens, causal=False, return_residuals=True)
    o_p = o_p.astype(jnp.float32)
    iq = jnp.arange(S)[:, None]
    mask = (jnp.arange(S)[None, :] <= iq)[None, None]
    kr = _repeat_kv(k, H // KV).astype(jnp.float32)
    vr = _repeat_kv(v, H // KV).astype(jnp.float32)
    o_s, m_s, l_s = attention_partials(q.astype(jnp.float32), kr, vr, mask)
    out = merge_attention_partials([(o_p, m_p, l_p), (o_s, m_s, l_s)])
    out = linear(p["wo"], out.astype(x.dtype).reshape(B, S, H * dh))
    return out, seg_kv


def decode_attention_packed_prefix(p, cfg: ModelConfig, x, packed_kv,
                                   sk_cache, sv_cache, pos, *, bits: int,
                                   group: int, chunk_tokens: int):
    """One-token attention over packed prefix + fp suffix cache.

    The decode form of `attention_packed_prefix`: the prefix stays
    quantized-resident (read by the fused `decode_attention_quant`
    kernel); only this request's *suffix* lives in an fp
    cache [B, S_suf, KV, dh], written at ``pos - P`` like
    `decode_attention` writes at ``pos``.  Returns (out [B,1,d],
    (sk_cache, sv_cache))."""
    from repro.kernels import ops as kernel_ops

    B = x.shape[0]
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k_q, v_q, k_scales, v_scales = packed_kv
    P = k_q.shape[1]
    q, k, v = project_qkv(p, cfg, x)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)
    spos = pos - P  # suffix-local write slot

    def upd(cache, new):
        return jax.vmap(
            lambda c, n, p_: jax.lax.dynamic_update_slice(c, n, (p_, 0, 0))
        )(cache, new, spos)

    sk_cache = upd(sk_cache, k.astype(sk_cache.dtype))
    sv_cache = upd(sv_cache, v.astype(sv_cache.dtype))
    if k_q.shape[0] != B:
        k_q, v_q, k_scales, v_scales = (
            jnp.broadcast_to(a, (B,) + a.shape[1:])
            for a in (k_q, v_q, k_scales, v_scales))
    lengths = jnp.full((B,), P, jnp.int32)
    o_p, m_p, l_p = kernel_ops.decode_attention_quant_op(
        q[:, 0], k_q, v_q, k_scales, v_scales, lengths, bits=bits,
        group=group, chunk_tokens=chunk_tokens, return_residuals=True)
    o_p = o_p.astype(jnp.float32)[:, None]  # [B,1,H,dh]
    m_p, l_p = m_p[:, None], l_p[:, None]
    Ss = sk_cache.shape[1]
    mask = (jnp.arange(Ss)[None, :] <= spos[:, None])[:, None, None, :]
    o_s, m_s, l_s = attention_partials(
        q.astype(jnp.float32),
        _repeat_kv(sk_cache.astype(jnp.float32), H // KV),
        _repeat_kv(sv_cache.astype(jnp.float32), H // KV), mask)
    out = merge_attention_partials([(o_p, m_p, l_p), (o_s, m_s, l_s)])
    out = linear(p["wo"], out.astype(x.dtype).reshape(B, 1, H * dh))
    return out, (sk_cache, sv_cache)


def _decode_scores_blocked(q, k_cache, v_cache, pos, n_blocks: int):
    """Flash-decoding expressed in shardable XLA ops (§Perf optimization O3).

    The cache sequence dim is viewed as [n_blocks, S/n_blocks]; every
    per-block partial (m, l, o) treats the block index as a BATCH dim, so a
    sequence-sharded cache (S over 'model') keeps all heavy work local and
    only the tiny [B,H]-sized partial merge crosses the mesh — replacing the
    full-cache all-gather GSPMD otherwise inserts for softmax.

    q: [B,H,dh]; caches: [B,S,KV,dh]; pos: [B] -> [B,H,dh].
    """
    B, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    nb = n_blocks
    sb = S // nb
    rep = H // KV
    kb = k_cache.reshape(B, nb, sb, KV, dh)
    vb = v_cache.reshape(B, nb, sb, KV, dh)
    qg = q.reshape(B, KV, rep, dh)
    s = jnp.einsum("bkrd,bnskd->bkrns", qg, kb,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    cols = (jnp.arange(nb)[:, None] * sb + jnp.arange(sb)[None, :])
    valid = cols[None] <= pos[:, None, None]  # [B,nb,sb]
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)  # [B,KV,rep,nb]
    safe = jnp.where(jnp.isfinite(m_blk), m_blk, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
    l_blk = jnp.sum(p, axis=-1)  # [B,KV,rep,nb]
    o_blk = jnp.einsum("bkrns,bnskd->bkrnd", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
    # tiny cross-block merge (this is the only part that crosses shards)
    m_g = jnp.max(m_blk, axis=-1, keepdims=True)
    w = jnp.where(jnp.isfinite(m_blk), jnp.exp(m_blk - m_g), 0.0)
    denom = jnp.sum(w * l_blk, axis=-1)  # [B,KV,rep]
    num = jnp.sum(w[..., None] * o_blk, axis=-2)  # [B,KV,rep,dh]
    out = num / jnp.maximum(denom, 1e-30)[..., None]
    return out.reshape(B, H, dh)


def decode_attention(p, cfg: ModelConfig, x, k_cache, v_cache, pos,
                     *, cross: bool = False, use_rope: bool = True,
                     cache_len_mask: Optional[jnp.ndarray] = None):
    """One-token attention against a [B, S, KV, dh] cache.

    ``pos``: [B] int32 — index of the new token.  Returns (out [B,1,d],
    updated (k_cache, v_cache)); for cross-attention the cache is read-only.
    """
    B = x.shape[0]
    H, KV, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = project_qkv(p, cfg, x)
    if not cross:
        if use_rope:
            q = rope(q, pos[:, None], cfg.rope_theta)
            k = rope(k, pos[:, None], cfg.rope_theta)
        # write the new token's KV at pos (per batch row)
        def upd(cache, new):
            return jax.vmap(
                lambda c, n, p_: jax.lax.dynamic_update_slice(c, n, (p_, 0, 0))
            )(cache, new, pos)
        k_cache = upd(k_cache, k.astype(k_cache.dtype))
        v_cache = upd(v_cache, v.astype(v_cache.dtype))
        S = k_cache.shape[1]
        if cfg.decode_impl == "blocked" and S % cfg.decode_blocks == 0:
            out = _decode_scores_blocked(q[:, 0], k_cache, v_cache, pos,
                                         cfg.decode_blocks).astype(x.dtype)
            out = linear(p["wo"], out.reshape(B, 1, H * dh))
            return out, (k_cache, v_cache)
        mask = (jnp.arange(S)[None, :] <= pos[:, None])[:, None, None, :]
    else:
        S = k_cache.shape[1]
        mask = jnp.ones((B, 1, 1, S), dtype=bool)
        if cache_len_mask is not None:
            mask = cache_len_mask[:, None, None, :]
    out = attention_scores(q, _repeat_kv(k_cache.astype(q.dtype), H // KV),
                           _repeat_kv(v_cache.astype(q.dtype), H // KV),
                           mask, cfg.logit_softcap)
    out = linear(p["wo"], out.reshape(B, 1, H * dh))
    return out, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             d_model: Optional[int] = None, kind: Optional[str] = None):
    d = d_model or cfg.d_model
    ff = d_ff or cfg.d_ff
    kind = kind or cfg.mlp_kind
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"wi_gate": init_linear(k1, d, ff, pdt(cfg)),
                "wi_up": init_linear(k2, d, ff, pdt(cfg)),
                "wo": init_linear(k3, ff, d, pdt(cfg), scale=ff ** -0.5)}
    return {"wi": init_linear(k1, d, ff, pdt(cfg)),
            "wo": init_linear(k3, ff, d, pdt(cfg), scale=ff ** -0.5)}


def mlp(p, x, kind: str = "swiglu"):
    # ``kind`` is static (not part of the pytree) so layer params stay
    # scan-stackable.
    if kind == "swiglu":
        h = jax.nn.silu(linear(p["wi_gate"], x)) * linear(p["wi_up"], x)
    elif kind == "geglu":
        h = jax.nn.gelu(linear(p["wi_gate"], x)) * linear(p["wi_up"], x)
    else:
        h = jax.nn.gelu(linear(p["wi"], x))
    return linear(p["wo"], h)


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------
def init_embedding(key, cfg: ModelConfig):
    p = {"table": _normal(key, (cfg.padded_vocab, cfg.d_model), 0.02, pdt(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = _normal(jax.random.fold_in(key, 1),
                               (cfg.padded_vocab, cfg.d_model), 0.02, pdt(cfg))
    return p


def embed(p, cfg: ModelConfig, tokens):
    x = p["table"].astype(dt(cfg))[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dt(cfg))
    return x


def logits(p, cfg: ModelConfig, x):
    table = p.get("unembed", p["table"])
    out = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        out = jnp.where(pad[None, None, :], jnp.finfo(jnp.float32).min, out)
    return out


def cross_entropy(logit_f32: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy in fp32; labels [B,S] int32."""
    logz = jax.nn.logsumexp(logit_f32, axis=-1)
    gold = jnp.take_along_axis(logit_f32, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
