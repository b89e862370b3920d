"""What every architecture's plain reference shares: the rounding of the
stated wire codec, the fp8 rounding of the control, RMSNorm, causal
attention in blocks of query rows, the output head in blocks of the
vocabulary, and the logit gaps that are compared.

Each architecture's reference is ``archs/<model_type>.py``'s
``Reference``; it is written from the configuration file alone, imports
nothing of the program, and runs in float32 with ``highest`` matmul
precision, one layer at a time.

The codec rounds per chunk of ``chunk_tokens`` tokens and per ``group``
consecutive channels of the flattened heads, one scale = absmax /
(2^(bits-1) - 1) rounded to float16, values rounded half to even and
clipped to +-(2^(bits-1) - 1).

``quant="fp8"`` is the control: every matmul operand (weights,
activations, queries, keys, values, attention probabilities) is rounded
to float8 e4m3 with a per-tensor scale before the product, the next
precision below the bfloat16 the configurations state.  Where attention
runs in several blocks of query rows, the probabilities take one scale
per block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384
# float32 scores in one block of query rows (1 GiB): the largest a cell
# held before attention was blocked (qwen3-0.6b's 4,096-token section,
# 16 x 4096 x 4096), so those cells run in one block
SCORE_BLOCK = 1 << 28


def _fq(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def codec_round(x, bits: int, group: int, chunk: int):
    """x [P, ...] -> x quantized and dequantized as the stated codec does
    (see the module docstring); the channels after the first axis are
    flattened into groups."""
    P = x.shape[0]
    qmax = float((1 << (bits - 1)) - 1)
    xg = x.reshape(P // chunk, chunk, -1, group)
    absmax = jnp.max(jnp.abs(xg), axis=(1, 3), keepdims=True)
    f16max = float(jnp.finfo(jnp.float16).max)
    s = jnp.minimum(absmax / qmax, f16max).astype(jnp.float16).astype(
        jnp.float32)
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(xg / s), -qmax, qmax)
    return (q * s).reshape(x.shape)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def causal_attention(q, k, v, quant=None):
    """Softmax attention of queries q [S, G, R, dh] (R query heads to each
    of G key heads) at the last S of the T positions of keys k and values
    v [T, G, dv], each query seeing the positions up to its own.  Returns
    [S, G, R, dv].  The rows run in as few equal blocks as keep a block's
    scores (G * R * rows * T) within ``SCORE_BLOCK``."""
    S, G, R, dh = q.shape
    T = k.shape[0]
    P = T - S
    q, k, v = _fq(q, quant), _fq(k, quant), _fq(v, quant)

    def block(qb, lo):
        s = jnp.einsum("sgrd,tgd->grst", qb, k)
        s = s / math.sqrt(dh)
        visible = (jnp.arange(T)[None, :]
                   <= (P + lo + jnp.arange(qb.shape[0]))[:, None])
        s = jnp.where(visible, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grst,tgd->sgrd", _fq(p, quant), v)

    blocks = -(-G * R * S * T // SCORE_BLOCK)
    if blocks <= 1:
        return block(q, 0)
    rows = -(-S // blocks)
    return jnp.concatenate([block(q[lo:lo + rows], lo)
                            for lo in range(0, S, rows)])


@functools.partial(jax.jit, static_argnames=("size", "eps", "quant"))
def _head_block(x, final_scale, table, start, *, size, eps, quant):
    """Logits of rows x [n, d] over vocabulary rows [start, start+size)."""
    blk = jax.lax.dynamic_slice_in_dim(table, start, size, 0)
    h = _rms(x, final_scale, eps)
    return jnp.matmul(_fq(h, quant), _fq(blk.astype(jnp.float32), quant).T)


def head(rows, final_scale, table, V: int, eps: float,
         quant=None) -> jnp.ndarray:
    """Logits [n, V] of the final hidden rows [n, d] through the final
    RMSNorm and the first ``V`` rows of the output table, ``VOCAB_BLOCK``
    rows at a time."""
    size = min(VOCAB_BLOCK, V)
    starts = list(range(0, V - size, size)) + [V - size]
    pieces, end = [], 0
    with jax.default_matmul_precision("highest"):
        for s in starts:
            lg = _head_block(rows, final_scale, table, s, size=size, eps=eps,
                             quant=quant)
            pieces.append(lg[:, end - s:])
            end = s + size
    return jnp.concatenate(pieces, axis=1)


def pad_rows(tokens: np.ndarray) -> np.ndarray:
    """``tokens`` padded with zeros to a power of two (at least 64), so that
    few shapes compile; causality keeps the padding out of every real
    row."""
    S = len(tokens)
    padded = np.zeros(max(64, 1 << (S - 1).bit_length()), np.int32)
    padded[:S] = tokens
    return padded


def logit_gaps(ref_logits: jnp.ndarray, tokens) -> np.ndarray:
    """Per row, the reference's best logit - its logit of the token that
    was served there."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tok[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)
