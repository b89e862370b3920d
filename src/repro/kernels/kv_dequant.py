"""Pallas fused KV dequantization — the client-side decode hop of the
quantized wire codecs (DESIGN.md §Codec).

An aggregated layer payload lands as N per-chunk quantized tiles plus one
fp16 scale row per matrix per chunk.  Attention wants model-dtype arrays;
this kernel fuses unpack (int4), int→float convert, and the scale multiply
into one VMEM pass, so the dequantized KV never round-trips HBM in a
temporary integer form.

Every body here is written for the TPU's (8, 128) vector tiling:

* tiles are 2-D — rows on sublanes, channels on lanes — and no reshape ever
  splits or merges the lane axis;
* fp16 scale rows cross into the kernel as their uint16 bit patterns
  (`scale_bits`; v5e cannot load fp16 vectors) and are widened to fp32 by
  integer ops (`_f16_bits_to_f32`), exactly;
* group-wise scales (one per ``group`` consecutive channels) and the int4
  nibble interleave are expanded by multiplying with 0/1 selection matrices
  built from iotas.  Each output element has exactly one non-zero term, so
  the products are exact: scales at HIGHEST precision, nibble values (small
  integers) at any precision.

`dequant_tile` is the shared inner loop of the fused quantized-KV attention
kernels (`decode_attention_quant`, `flash_attention_quant`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def scale_bits(scales):
    """fp16 scale rows -> their uint16 bit patterns (a free XLA bitcast)."""
    return jax.lax.bitcast_convert_type(scales.astype(jnp.float16), jnp.uint16)


def _f16_bits_to_f32(b):
    """uint16 fp16 bit patterns -> exact fp32 values, with integer ops only."""
    b = b.astype(jnp.int32)
    exp = (b >> 10) & 0x1F
    man = b & 0x3FF
    # normal: rebias the exponent (15 -> 127) and widen the mantissa
    normal = jax.lax.bitcast_convert_type(((exp + 112) << 23) | (man << 13),
                                          jnp.float32)
    special = jax.lax.bitcast_convert_type((0xFF << 23) | (man << 13),
                                           jnp.float32)
    mag = jnp.where(exp == 0, man.astype(jnp.float32) * (2.0 ** -24),
                    jnp.where(exp == 0x1F, special, normal))
    return jnp.where((b >> 15) != 0, -mag, mag)


def _div(x, d: int):
    """Vector integer division by a static positive ``d`` (a shift when
    ``d`` is a power of two, which is every group width the codecs use)."""
    if d & (d - 1) == 0:
        return x >> (d.bit_length() - 1)
    return x // d


def _select(rows: int, cols: int, col_to_row):
    """0/1 fp32 matrix [rows, cols] with E[j, c] = (j == col_to_row(c))."""
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return (j == col_to_row(c)).astype(jnp.float32)


def _expand_scales(s, width: int, col_to_group):
    """Scale rows [n, ng] (fp32) -> per-channel [n, width]: channel c takes
    scale column ``col_to_group(c)``."""
    E = _select(s.shape[1], width, col_to_group)
    return jax.lax.dot_general(s, E, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _unpack_nibbles(q):
    """Biased int4 pairs (n = v + 8, even channel in the low nibble) ->
    (lo, hi) fp32 planes of the same shape."""
    q = q.astype(jnp.int32)
    lo, hi = (q & 0xF) - 8, (q >> 4) - 8
    return lo.astype(jnp.float32), hi.astype(jnp.float32)


def _scale_rows(x, sw):
    """x [rows, w] times per-window scales sw [n, w]: tile row r uses window
    r // (rows // n).  The split keeps the lane axis whole."""
    n = sw.shape[0]
    if n == 1:
        return x * sw
    rows, w = x.shape
    return (x.reshape(n, rows // n, w) * sw[:, None, :]).reshape(rows, w)


def dequant_tile(q, s, *, bits: int, group: int, dh: int, head0, col0):
    """Dequantize one packed cache tile, inside a kernel body.

    ``q``: [rows, w] — the lane block of a [S, KV*dh'] cache that holds
    whole heads starting at head ``head0``: one int8 head (w = dh), or one
    or two heads of biased nibble pairs (dh' = dh/2) when ``bits == 4``.
    ``s``: [ncb, ng'] uint16 fp16 scale bits, whose column 0 is scale group
    ``col0`` of the flattened KV*dh channel axis; ``rows`` spans ``ncb``
    whole scale windows (rows % ncb == 0).

    Returns one fp32 [rows, dh] tile per head.  Int8 tiles keep channel
    order.  Int4 tiles come *channel-permuted* — the head's even channels,
    then its odd ones (`int4_perm`) — because that is what the nibble planes
    hold without any lane shuffle; callers permute queries the same way and
    un-permute outputs (`decode_attention`, `flash_attention_quant`).
    """
    s = _f16_bits_to_f32(s)
    half = dh // 2
    if bits == 4:
        lo, hi = _unpack_nibbles(q)
        hpb = 2 * q.shape[1] // dh
        if hpb == 1:
            tiles = [jnp.concatenate([lo, hi], axis=1)]
        else:  # two heads per 128-lane block: swap their halves by a roll
            assert hpb == 2, (q.shape, dh)
            lane = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
            first = lane < half
            tiles = [jnp.where(first, lo, pltpu.roll(hi, half, 1)),
                     jnp.where(first, pltpu.roll(lo, half, 1), hi)]

        def chan(c):  # tile lane -> channel within the head
            return jnp.where(c < half, 2 * c, 2 * c - dh + 1)
    else:
        tiles = [q.astype(jnp.float32)]

        def chan(c):
            return c
    out = []
    for h, t in enumerate(tiles):
        base = (head0 + h) * dh
        if group == 1 and bits != 4 and s.shape[1] == dh:
            sw = s  # this head's own scale columns, lane-aligned
        else:
            sw = _expand_scales(
                s, dh, lambda c, base=base: _div(base + chan(c), group) - col0)
        out.append(_scale_rows(t, sw))
    return out


def int4_perm(x):
    """Channel order of an int4 `dequant_tile`: evens, then odds."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def int4_unperm(x):
    """Inverse of `int4_perm`."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# standalone dequant: [N, R, W'] chunk tiles -> [N, R, W]
# ---------------------------------------------------------------------------
def _dequant_kernel(q_ref, s_ref, o_ref, *, bits: int, group: int):
    W = o_ref.shape[1]
    s = _f16_bits_to_f32(s_ref[...])  # [nb, ng]
    sw = s if group == 1 else _expand_scales(s, W, lambda c: _div(c, group))
    if bits == 4:
        lo, hi = _unpack_nibbles(q_ref[...])  # [nb*R, W/2] each
        # interleave lo/hi into even/odd channels with 0/1 matmuls, one
        # lane block at a time (each output lane has one non-zero term)
        wb = 128 if lo.shape[1] % 128 == 0 else lo.shape[1]
        P_even = _select(wb, 2 * wb, lambda c: c >> 1)
        P_odd = P_even * (jax.lax.broadcasted_iota(
            jnp.int32, (wb, 2 * wb), 1) & 1).astype(jnp.float32)
        P_even = P_even - P_odd
        # small integers times 0/1: exact at any matmul precision
        dot = functools.partial(jax.lax.dot_general,
                                dimension_numbers=(((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        for i in range(lo.shape[1] // wb):
            a, b = i * wb, (i + 1) * wb
            x = dot(lo[:, a:b], P_even) + dot(hi[:, a:b], P_odd)
            o_ref[:, 2 * a:2 * b] = _scale_rows(
                x, sw[:, 2 * a:2 * b]).astype(o_ref.dtype)
    else:
        o_ref[...] = _scale_rows(q_ref[...].astype(jnp.float32),
                                 sw).astype(o_ref.dtype)


def _dequant_call(q, scales, *, bits: int, group: int, out_dtype,
                  interpret: bool):
    N, R, Wq = q.shape
    W = 2 * Wq if bits == 4 else Wq
    ng = W // group
    assert scales.shape == (N, ng), (q.shape, scales.shape, group)
    # chunks per grid step: whole sublane tiles of scale rows (8), or all
    nb = N if N <= 8 else 8
    kernel = functools.partial(_dequant_kernel, bits=bits, group=group)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(N, nb),),
        in_specs=[pl.BlockSpec((nb * R, Wq), lambda i: (i, 0)),
                  pl.BlockSpec((nb, ng), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((nb * R, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N * R, W), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q.reshape(N * R, Wq), scale_bits(scales))
    return out.reshape(N, R, W)


def kv_dequant(q, scales, *, group: int = 1, out_dtype=jnp.float32,
               interpret: bool = False) -> jnp.ndarray:
    """q: [N, R, W] int8; scales: [N, W/group] fp16 → [N, R, W]
    ``out_dtype``."""
    return _dequant_call(q, scales, bits=8, group=group, out_dtype=out_dtype,
                         interpret=interpret)


def kv_dequant_packed4(q_packed, scales, *, group: int = 1,
                       out_dtype=jnp.float32,
                       interpret: bool = False) -> jnp.ndarray:
    """q_packed: [N, R, W/2] uint8 (pairwise int4, `codec.ref.pack_int4`);
    scales: [N, W/group] fp16 → [N, R, W] ``out_dtype``."""
    return _dequant_call(q_packed, scales, bits=4, group=group,
                         out_dtype=out_dtype, interpret=interpret)
