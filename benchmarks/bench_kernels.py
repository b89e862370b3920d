"""Pallas kernel micro-bench: wall time (interpret mode on CPU — semantics
validation, not speed; Mosaic on TPU) and max deviation vs the pure-jnp
oracle.  Calls go through `kernels.ops`, which picks interpret mode from the
backend.

The fused dequant-attention rows additionally report the ISSUE's residency
acceptance numbers: packed-resident contexts-per-byte vs fp-resident
(``resident_ratio``), and the single-HBM-pass byte model (``fused_reads``
must equal the wire-resident footprint — each packed cache byte is read
exactly once; the composed path re-reads the expanded fp cache).

Run standalone:  PYTHONPATH=src python benchmarks/bench_kernels.py [--smoke]
                 [--json PATH]
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ops import (decode_attention_op as decode_attention,
                               decode_attention_quant_op as
                               decode_attention_quant,
                               flash_attention_op as flash_attention,
                               flash_attention_quant_op as
                               flash_attention_quant,
                               kv_gather_op as kv_gather)
from repro.kernels.residency import (cache_bytes, composed_decode_hbm_traffic,
                                     fused_decode_hbm_reads, residency_ratio)

try:
    from .common import row, timeit, write_json
except ImportError:  # standalone: python benchmarks/bench_kernels.py
    from common import row, timeit, write_json

KEY = jax.random.PRNGKey(0)


def _packed(key, B, S, KV, dh, NC, bits, group):
    """Synthetic wire-layout cache half: packed ints + per-chunk scales."""
    kq_, ks_ = jax.random.split(key)
    qmax = 127 if bits == 8 else 7
    if bits == 4:
        q = jax.random.randint(kq_, (B, S, KV, dh // 2), 0, 256,
                               jnp.int32).astype(jnp.uint8)
    else:
        q = jax.random.randint(kq_, (B, S, KV, dh), -127, 128,
                               jnp.int32).astype(jnp.int8)
    ng = KV * dh // group
    s = (jax.random.uniform(ks_, (B, NC, ng), minval=0.5, maxval=1.5)
         / qmax).astype(jnp.float16)
    return q, s


def run(smoke: bool = False) -> list[str]:
    rows = []
    # flash attention
    q = jax.random.normal(KEY, (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(KEY, (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(KEY, (1, 2, 256, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    err = float(jnp.abs(out - ref.ref_flash_attention(q, k, v)).max())
    wall = timeit(lambda: flash_attention(q, k, v, causal=True), repeat=3)
    flops = 4 * 256 * 256 * 4 * 64 / 2
    rows.append(row("kernel/flash_attn/256x4h", wall * 1e6,
                    f"max_err={err:.2e};flops={flops:.2e}"))

    # decode attention (ragged S: the trailing partial block rides the
    # lengths mask — the S % block_s hard-assert regression)
    S = 1024 + 8
    qd = jax.random.normal(KEY, (4, 8, 64), jnp.float32)
    kc = jax.random.normal(KEY, (4, S, 2, 64), jnp.float32)
    vc = jax.random.normal(KEY, (4, S, 2, 64), jnp.float32)
    lens = jnp.array([1000, 512, 64, S])
    outd = decode_attention(qd, kc, vc, lens, block_s=256)
    errd = float(jnp.abs(outd
                         - ref.ref_decode_attention(qd, kc, vc, lens)).max())
    walld = timeit(lambda: decode_attention(qd, kc, vc, lens, block_s=256),
                   repeat=3)
    rows.append(row("kernel/decode_attn/1k_ragged", walld * 1e6,
                    f"max_err={errd:.2e};cache_MB={kc.nbytes*2/1e6:.1f}"))

    # fused dequant-attention: the cache stays packed in HBM end to end
    B, H, KV, dh, G = 2, 8, 2, 64, 64
    Sq = 256 if smoke else 1024
    for bits, group in ((8, 64), (4, 64)):
        kq, ks = _packed(KEY, B, Sq, KV, dh, Sq // G, bits, group)
        vq, vs = _packed(jax.random.PRNGKey(1), B, Sq, KV, dh, Sq // G, bits,
                         group)
        qq = jax.random.normal(KEY, (B, H, dh), jnp.float32)
        qlens = jnp.array([Sq, Sq - G // 2])
        args = dict(bits=bits, group=group, chunk_tokens=G)
        outq = decode_attention_quant(qq, kq, vq, ks, vs, qlens, block_s=256,
                                      **args)
        errq = float(jnp.abs(outq - ref.ref_decode_attention_quant(
            qq, kq, vq, ks, vs, qlens, **args)).max())
        wallq = timeit(lambda: decode_attention_quant(
            qq, kq, vq, ks, vs, qlens, block_s=256, **args),
            repeat=3)
        # the residency acceptance numbers for this shape (one layer, fp16
        # resident baseline)
        cb = cache_bytes(Sq, KV, dh, bits=bits, group=group, chunk_tokens=G)
        ratio = residency_ratio(cb, peak=True)
        reads = fused_decode_hbm_reads(cb, Sq, chunk_tokens=G, block_s=256)
        assert reads == cb.wire_resident, "fused decode must be single-pass"
        rows.append(row(
            f"kernel/decode_attn_quant/int{bits}", wallq * 1e6,
            f"max_err={errq:.2e};resident_ratio={ratio:.2f};"
            f"fused_reads={reads};"
            f"composed_traffic={composed_decode_hbm_traffic(cb)}"))

        qp = jax.random.normal(KEY, (B, G, H, dh), jnp.float32)
        outf = flash_attention_quant(qp, kq, vq, ks, vs, causal=True,
                                     q_offset=Sq, block_q=G, block_k=256,
                                     **args)
        errf = float(jnp.abs(outf - ref.ref_flash_attention_quant(
            qp, kq, vq, ks, vs, causal=True, q_offset=Sq, **args)).max())
        wallf = timeit(lambda: flash_attention_quant(
            qp, kq, vq, ks, vs, causal=True, q_offset=Sq, block_q=G,
            block_k=256, **args), repeat=3)
        rows.append(row(f"kernel/flash_attn_quant/int{bits}", wallf * 1e6,
                        f"max_err={errf:.2e}"))

    # kv gather (ObjectCache on-device aggregation)
    pool = jax.random.normal(KEY, (256, 16, 256), jnp.float32)
    idx = jax.random.randint(KEY, (64,), 0, 256)
    outg = kv_gather(pool, idx)
    errg = float(jnp.abs(outg - ref.ref_kv_gather(pool, idx)).max())
    wallg = timeit(lambda: kv_gather(pool, idx), repeat=3)
    rows.append(row("kernel/kv_gather/64of256", wallg * 1e6,
                    f"max_err={errg:.2e};bytes={outg.nbytes}"))
    return rows


def main(argv: list[str]) -> int:
    smoke = "--smoke" in argv
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            print("--json requires a PATH argument", file=sys.stderr)
            return 2
        json_path = argv[i + 1]
    print("name,us_per_call,derived")
    lines = []
    for line in run(smoke=smoke):
        print(line, flush=True)
        lines.append(line)
    if json_path is not None:
        write_json(json_path, "bench_kernels", lines)
        print(f"# json: {len(lines)} rows -> {json_path}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
