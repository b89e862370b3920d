"""KV wire-codec subsystem tests (DESIGN.md §Codec).

Covers: the codec spec grammar, wire-size arithmetic (constant and
variable-rate), quantization reference primitives (per-channel and
group-wise), chunk round-trips (identity bit-exact, quantized bounded),
property-based round-trip/sizing/bijectivity over every registered codec,
descriptor v1/v2/v3 wire formats + committed golden fixtures, server-side
aggregation of *encoded* objects via the size table, the fused Pallas
dequant kernels vs the numpy reference, the mixed-bit allocator, byte
accounting through the TTFT closed forms / hybrid planner / bandwidth pool,
and single-request cluster conformance with codec-adjusted byte counts.
"""
import math
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.codec import (get_codec, greedy_bit_map, layer_quant_error,
                         mixed_codec_name)
from repro.codec import ref as cref
from repro.core import (CODEC_WIRE_IDS, Delivery, Descriptor, Gateway,
                        InMemoryStore, KVSpec, StorageServer, chunk_keys,
                        codec_wire_id, descriptor_overhead_bytes, layer_range,
                        make_descriptor, parse_codec)
from repro.core.compute_model import PaperComputeModel
from repro.core.scheduler import Policy, allocate
from repro.core.simulator import ServingSimulator, WorkloadRequest
from repro.core.transport import S3_RDMA_AGG
from repro.hybrid.planner import plan_split, split_ttft
from repro.hybrid.policy import HybridReplanner
from repro.kernels import ops as kernel_ops

GBPS = 1e9 / 8
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# one spec string per registered codec family, with parameters legal for the
# small test geometries (explicit groups; the defaults assume width >= 128)
ALL_FAMILY_CODECS = ("identity", "int8", "int4", "gw8/g4", "gw4/g4",
                     "mixed/848/g4")
MIXED32 = "mixed/" + "8" * 8 + "4" * 24 + "/g128"  # paper-geometry bit map


def _spec(codec, L=3, G=8, KV=2, dh=4, p=2):
    return KVSpec(num_layers=L, chunk_tokens=G, num_kv_heads=KV, head_dim=dh,
                  dtype_bytes=p, codec=codec)


def _chunk_kv(spec, seed=0):
    import ml_dtypes
    rng = np.random.default_rng(seed)
    shape = (spec.num_layers, spec.chunk_tokens, spec.width)
    k = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)
    v = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)
    return k, v


# ---------------------------------------------------------------------------
# wire-size arithmetic
# ---------------------------------------------------------------------------
class TestWireSizing:
    def test_identity_wire_equals_raw(self):
        spec = _spec("identity")
        assert spec.wire_per_layer_chunk_bytes == spec.per_layer_chunk_bytes
        assert spec.wire_chunk_bytes == spec.chunk_bytes
        assert spec.wire_ratio == 1.0
        assert spec.matched_wire_bytes(5) == spec.matched_payload_bytes(5)

    @pytest.mark.parametrize("codec,bits", [("int8", 8), ("int4", 4)])
    def test_quant_wire_arithmetic(self, codec, bits):
        spec = _spec(codec, G=64, KV=8, dh=128)
        W = spec.width
        scale_bytes = 2 * W * 2
        payload = 2 * (64 * W * bits // 8)
        assert spec.scale_bytes_per_layer == scale_bytes
        assert spec.wire_per_layer_chunk_bytes == scale_bytes + payload
        assert spec.wire_ratio < 1.0

    def test_int4_reaches_paper_reduction_at_g64(self):
        """Acceptance bar: >= 3.5x wire-byte reduction at G=64."""
        spec = _spec("int4", G=64, KV=8, dh=128)
        assert spec.per_layer_chunk_bytes / spec.wire_per_layer_chunk_bytes \
            >= 3.5

    def test_unknown_codec_rejected(self):
        with pytest.raises(ValueError, match="unknown wire codec"):
            _spec("zstd")
        with pytest.raises(ValueError, match="unknown wire codec"):
            get_codec("zstd")

    def test_every_registered_codec_has_wire_id(self):
        for name in ("identity", "int8", "int4"):
            assert get_codec(name).codec_id == CODEC_WIRE_IDS[name]

    def test_layer_range_follows_wire_stride(self):
        spec = _spec("int4")
        S = spec.wire_per_layer_chunk_bytes
        assert layer_range(2, spec) == (2 * S, 3 * S)


# ---------------------------------------------------------------------------
# reference primitives
# ---------------------------------------------------------------------------
class TestRefPrimitives:
    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantize_error_bounded_by_half_scale(self, bits):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((5, 16, 6)).astype(np.float32)
        q, scales = cref.quantize_per_channel(x, bits)
        y = cref.dequantize_per_channel(q, scales)
        s = scales.astype(np.float32)[..., None, :]
        # nearest-value rounding plus the fp16 scale rounding slack
        assert np.all(np.abs(y - x) <= 0.51 * s + 1e-7)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quantize_range(self, bits):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((4, 32, 8)).astype(np.float32) * 100
        q, _ = cref.quantize_per_channel(x, bits)
        qmax = cref.qmax_for_bits(bits)
        assert q.min() >= -qmax and q.max() <= qmax

    def test_huge_channel_scale_stays_finite(self):
        """absmax beyond qmax*fp16_max must clamp the stored scale, not
        overflow it to inf (which would dequantize to 0*inf = NaN)."""
        x = np.zeros((1, 8, 4), np.float32)
        x[0, :, 0] = 9e6  # > 127 * 65504
        q, scales = cref.quantize_per_channel(x, 8)
        assert np.isfinite(scales.astype(np.float32)).all()
        y = cref.dequantize_per_channel(q, scales)
        assert np.isfinite(y).all()
        assert y[0, 0, 0] == pytest.approx(127 * 65504.0, rel=1e-3)

    def test_zero_channel_is_exact(self):
        x = np.zeros((2, 8, 4), np.float32)
        q, scales = cref.quantize_per_channel(x, 8)
        assert not q.any() and not scales.astype(np.float32).any()
        np.testing.assert_array_equal(cref.dequantize_per_channel(q, scales), x)

    def test_pack_unpack_int4_roundtrip(self):
        rng = np.random.default_rng(2)
        q = rng.integers(-8, 8, size=(3, 7, 10)).astype(np.int8)
        np.testing.assert_array_equal(cref.unpack_int4(cref.pack_int4(q)), q)

    def test_pack_int4_odd_width_rejected(self):
        with pytest.raises(ValueError, match="even width"):
            cref.pack_int4(np.zeros((2, 3), np.int8))


# ---------------------------------------------------------------------------
# chunk round-trips
# ---------------------------------------------------------------------------
class TestChunkRoundtrip:
    def test_identity_bit_exact(self):
        spec = _spec("identity")
        k, v = _chunk_kv(spec)
        codec = get_codec("identity")
        buf = codec.encode_chunk(k, v, spec)
        assert len(buf) == spec.wire_chunk_bytes
        for l in range(spec.num_layers):
            lo, hi = layer_range(l, spec)
            kk, vv = codec.decode_layer_payload(buf[lo:hi], 1, spec, k.dtype)
            np.testing.assert_array_equal(kk.view(np.uint16),
                                          k[l].view(np.uint16))
            np.testing.assert_array_equal(vv.view(np.uint16),
                                          v[l].view(np.uint16))

    def test_identity_accepts_wire_words(self):
        """bf16 may cross the boundary pre-viewed as uint16 — same bytes."""
        spec = _spec("identity")
        k, v = _chunk_kv(spec)
        codec = get_codec("identity")
        assert codec.encode_chunk(k, v, spec) == codec.encode_chunk(
            k.view(np.uint16), v.view(np.uint16), spec)

    @pytest.mark.parametrize("codec_name", ["int8", "int4"])
    def test_quant_roundtrip_bounded(self, codec_name):
        spec = _spec(codec_name)
        k, v = _chunk_kv(spec)
        codec = get_codec(codec_name)
        buf = codec.encode_chunk(k, v, spec)
        assert len(buf) == spec.wire_chunk_bytes
        qmax = cref.qmax_for_bits(codec.bits)
        for l in range(spec.num_layers):
            lo, hi = layer_range(l, spec)
            kk, _ = codec.decode_layer_payload(buf[lo:hi], 1, spec, np.float32)
            x = k[l].astype(np.float32)
            bound = 0.51 * np.abs(x).max(axis=0) / qmax + 1e-7
            assert np.all(np.abs(kk - x) <= bound[None, :])

    @pytest.mark.parametrize("codec_name", ["int8", "int4"])
    def test_quant_aggregated_payload_order(self, codec_name):
        """An aggregated payload of N chunks decodes to the chunks' slices
        concatenated in prefix order."""
        spec = _spec(codec_name)
        codec = get_codec(codec_name)
        k0, v0 = _chunk_kv(spec, seed=0)
        k1, v1 = _chunk_kv(spec, seed=1)
        b0 = codec.encode_chunk(k0, v0, spec)
        b1 = codec.encode_chunk(k1, v1, spec)
        l = 1
        lo, hi = layer_range(l, spec)
        payload = b0[lo:hi] + b1[lo:hi]
        kk, vv = codec.decode_layer_payload(payload, 2, spec, np.float32)
        ka, _ = codec.decode_layer_payload(b0[lo:hi], 1, spec, np.float32)
        kb, _ = codec.decode_layer_payload(b1[lo:hi], 1, spec, np.float32)
        G = spec.chunk_tokens
        np.testing.assert_array_equal(kk[:G], ka)
        np.testing.assert_array_equal(kk[G:], kb)

    def test_int4_odd_width_rejected(self):
        # rejected at spec construction now — 4-bit packing is pairwise
        with pytest.raises(ValueError, match="even width"):
            KVSpec(2, 4, 1, 3, 2, codec="int4")  # width 3


# ---------------------------------------------------------------------------
# descriptor + aggregation over encoded objects
# ---------------------------------------------------------------------------
class TestDescriptorAndAggregation:
    @pytest.mark.parametrize("codec_name", ["identity", "int8", "int4"])
    def test_descriptor_carries_codec(self, codec_name):
        spec = _spec(codec_name)
        keys = chunk_keys(np.arange(32), spec.chunk_tokens)
        d = make_descriptor(keys, spec, Delivery.LAYERWISE)
        assert d.codec_id == spec.codec_id
        assert d.per_layer_chunk_bytes == spec.wire_per_layer_chunk_bytes
        assert d.total_bytes == spec.matched_wire_bytes(len(keys))
        d2 = Descriptor.from_wire(d.to_wire())
        assert d2 == d

    @pytest.mark.parametrize("codec_name", ["identity", "int8", "int4"])
    def test_layerwise_aggregation_of_encoded_chunks(self, codec_name):
        """The storage server range-reads the *encoded* stride and delivers
        compressed layer payloads whose decode matches per-chunk decode."""
        spec = _spec(codec_name)
        codec = get_codec(codec_name)
        store = InMemoryStore()
        toks = np.arange(4 * spec.chunk_tokens)
        keys = chunk_keys(toks, spec.chunk_tokens)
        chunks = {}
        for i, key in enumerate(keys):
            k, v = _chunk_kv(spec, seed=i)
            chunks[key] = codec.encode_chunk(k, v, spec)
            store.put(key, chunks[key])
        desc = make_descriptor(keys, spec, Delivery.LAYERWISE)
        res = StorageServer(store, S3_RDMA_AGG).execute_layerwise(desc)
        S = spec.wire_per_layer_chunk_bytes
        assert len(res.payloads) == spec.num_layers
        for l, payload in enumerate(res.payloads):
            assert len(payload) == len(keys) * S
            want = b"".join(chunks[key][l * S:(l + 1) * S] for key in keys)
            assert payload == want
        assert all(e.nbytes == len(keys) * S for e in res.events)

    @pytest.mark.parametrize("codec_name", ["identity", "int4"])
    def test_chunkwise_equals_layerwise_payloads(self, codec_name):
        spec = _spec(codec_name)
        codec = get_codec(codec_name)
        store = InMemoryStore()
        keys = chunk_keys(np.arange(3 * spec.chunk_tokens), spec.chunk_tokens)
        for i, key in enumerate(keys):
            k, v = _chunk_kv(spec, seed=i)
            store.put(key, codec.encode_chunk(k, v, spec))
        lw = StorageServer(store, S3_RDMA_AGG).execute_layerwise(
            make_descriptor(keys, spec, Delivery.LAYERWISE))
        cw = StorageServer(store, S3_RDMA_AGG).execute_chunkwise(
            make_descriptor(keys, spec, Delivery.CHUNKWISE))
        assert lw.payloads == cw.payloads

    @pytest.mark.parametrize("codec_name", ["identity", "int4"])
    def test_gateway_objectcache_path(self, codec_name):
        spec = _spec(codec_name)
        codec = get_codec(codec_name)
        store = InMemoryStore()
        keys = chunk_keys(np.arange(2 * spec.chunk_tokens), spec.chunk_tokens)
        for i, key in enumerate(keys):
            k, v = _chunk_kv(spec, seed=i)
            store.put(key, codec.encode_chunk(k, v, spec))
        gw = Gateway(store)
        desc = make_descriptor(keys, spec, Delivery.LAYERWISE)
        res = gw.objectcache_get(desc.to_wire())
        assert len(res.payloads) == spec.num_layers
        assert all(len(p) == 2 * spec.wire_per_layer_chunk_bytes
                   for p in res.payloads)


# ---------------------------------------------------------------------------
# fused dequant kernels vs the numpy reference
# ---------------------------------------------------------------------------
class TestDequantKernels:
    @pytest.mark.parametrize("N,R,W", [(1, 8, 8), (3, 16, 8), (5, 4, 128)])
    def test_int8_kernel_matches_ref(self, N, R, W):
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        q = rng.integers(-127, 128, size=(N, R, W)).astype(np.int8)
        scales = (rng.random((N, W)) * 0.1 + 1e-3).astype(np.float16)
        out = np.asarray(kernel_ops.kv_dequant_op(jnp.asarray(q),
                                                  jnp.asarray(scales)))
        want = cref.dequantize_per_channel(
            q.transpose(0, 1, 2), scales)  # [N, R, W] * [N, W]
        np.testing.assert_array_equal(out, want)

    @pytest.mark.parametrize("N,R,W", [(1, 8, 8), (4, 8, 64)])
    def test_packed4_kernel_matches_ref(self, N, R, W):
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        q = rng.integers(-7, 8, size=(N, R, W)).astype(np.int8)
        packed = cref.pack_int4(q)
        scales = (rng.random((N, W)) * 0.1 + 1e-3).astype(np.float16)
        out = np.asarray(kernel_ops.kv_dequant_packed4_op(
            jnp.asarray(packed), jnp.asarray(scales)))
        want = cref.dequantize_per_channel(q, scales)
        np.testing.assert_array_equal(out, want)

    def test_out_dtype(self):
        import jax.numpy as jnp
        q = np.ones((1, 2, 4), np.int8)
        s = np.full((1, 4), 0.5, np.float16)
        out = kernel_ops.kv_dequant_op(jnp.asarray(q), jnp.asarray(s),
                                       out_dtype=jnp.bfloat16)
        assert out.dtype == jnp.bfloat16

    @pytest.mark.parametrize("group", [2, 4])
    @pytest.mark.parametrize("N,R,W", [(1, 8, 8), (3, 4, 16)])
    def test_grouped_kernel_matches_ref(self, group, N, R, W):
        """Group-wise scale rows broadcast inside the kernel must equal the
        numpy grouped dequant exactly, int8 and packed-int4 alike."""
        import jax.numpy as jnp
        rng = np.random.default_rng(7)
        scales = (rng.random((N, W // group)) * 0.1 + 1e-3).astype(np.float16)
        q8 = rng.integers(-127, 128, size=(N, R, W)).astype(np.int8)
        out = np.asarray(kernel_ops.kv_dequant_op(
            jnp.asarray(q8), jnp.asarray(scales), group=group))
        np.testing.assert_array_equal(
            out, cref.dequantize_grouped(q8, scales, group))
        q4 = rng.integers(-7, 8, size=(N, R, W)).astype(np.int8)
        out = np.asarray(kernel_ops.kv_dequant_packed4_op(
            jnp.asarray(cref.pack_int4(q4)), jnp.asarray(scales), group=group))
        np.testing.assert_array_equal(
            out, cref.dequantize_grouped(q4, scales, group))

    def test_device_decode_matches_host_decode(self):
        import jax.numpy as jnp
        from repro.serving.kv_chunks import (layer_payload_to_device_kv,
                                             layer_payload_to_kv)
        for codec_name in ("int8", "int4", "gw8/g4", "gw4/g8",
                           "mixed/848/g4"):
            spec = _spec(codec_name)
            codec = get_codec(codec_name)
            k, v = _chunk_kv(spec, seed=3)
            buf = codec.encode_chunk(k, v, spec)
            for l in range(spec.num_layers):
                lo, hi = layer_range(l, spec)
                payload = buf[lo:hi]
                kh, vh = layer_payload_to_kv(payload, 1, spec, jnp.float32, l)
                kd, vd = layer_payload_to_device_kv(payload, 1, spec,
                                                    jnp.float32, l)
                np.testing.assert_array_equal(np.asarray(kd), kh)
                np.testing.assert_array_equal(np.asarray(vd), vh)


# ---------------------------------------------------------------------------
# byte accounting: closed forms, scheduler demand, hybrid crossover
# ---------------------------------------------------------------------------
class TestByteAccounting:
    def test_flow_request_demand_scales_with_wire_ratio(self):
        w = WorkloadRequest("r", 16384, 0.875)
        base = ServingSimulator(codec="identity").flow_request(w)
        comp = ServingSimulator(codec="int4").flow_request(w)
        spec = ServingSimulator(codec="int4").kv_spec(64)
        assert comp.bytes_per_layer == pytest.approx(
            base.bytes_per_layer * spec.wire_ratio)
        assert comp.layer_compute_s == base.layer_compute_s

    @pytest.mark.parametrize("codec_name", ["int8", "int4"])
    def test_constrained_ttft_improves_under_compression(self, codec_name):
        w = WorkloadRequest("r", 16384, 0.875)
        rate = 2 * GBPS
        base = ServingSimulator(codec="identity").ttft_layerwise(
            w, rate_limit=rate).ttft_s
        comp = ServingSimulator(codec=codec_name).ttft_layerwise(
            w, rate_limit=rate).ttft_s
        assert comp < base

    def test_unconstrained_ttft_never_worse(self):
        w = WorkloadRequest("r", 65536, 0.875)
        base = ServingSimulator(codec="identity").ttft_layerwise(w).ttft_s
        comp = ServingSimulator(codec="int4").ttft_layerwise(w).ttft_s
        assert comp <= base + 1e-12

    def test_hybrid_crossover_shifts_toward_fetch(self):
        compute = PaperComputeModel()
        n = int(16384 * 0.875) // 64
        fetched = []
        for codec_name in ("identity", "int8", "int4"):
            spec = ServingSimulator(codec=codec_name).kv_spec(64)
            split = plan_split(16384, n, spec, compute, S3_RDMA_AGG,
                               rate=4 * GBPS)
            fetched.append(split.fetch_chunks)
        assert fetched[0] <= fetched[1] <= fetched[2]
        assert fetched[0] < fetched[2]  # strictly interior shift at 4 Gbps

    def test_mixed_flow_demand_is_mean_stride(self):
        """Variable-rate codecs present a scalar per-layer demand (the mean
        encoded stride): s_i * L must recover the exact wire total."""
        w = WorkloadRequest("r", 16384, 0.875)
        sim = ServingSimulator(codec=MIXED32)
        spec = sim.kv_spec(64)
        fr = sim.flow_request(w)
        base = ServingSimulator(codec="identity").flow_request(w)
        assert fr.bytes_per_layer == pytest.approx(
            base.bytes_per_layer * spec.wire_ratio)
        n = int(16384 * 0.875) // 64
        assert n * spec.mean_wire_layer_bytes * spec.num_layers \
            == pytest.approx(n * spec.wire_chunk_bytes, abs=1e-6)

    @pytest.mark.parametrize("codec_name", ["identity", "int4", "gw4",
                                            MIXED32])
    def test_closed_form_matches_exhaustive_under_codec(self, codec_name):
        compute = PaperComputeModel()
        spec = ServingSimulator(codec=codec_name).kv_spec(64)
        n = int(16384 * 0.875) // 64
        for rate in (1 * GBPS, 8 * GBPS, None):
            cf = plan_split(16384, n, spec, compute, S3_RDMA_AGG, rate,
                            method="closed_form")
            ex = plan_split(16384, n, spec, compute, S3_RDMA_AGG, rate,
                            method="exhaustive")
            assert cf.ttft_s == pytest.approx(ex.ttft_s, abs=1e-12)

    @pytest.mark.parametrize("codec_name", ["int4", MIXED32])
    def test_replanner_recovers_chunks_from_wire_stride(self, codec_name):
        """HybridReplanner recovers the chunk count from the *wire* total;
        under any codec (variable-rate included) it must still be exact."""
        compute = PaperComputeModel()
        spec = ServingSimulator(codec=codec_name).kv_spec(64)
        rep = HybridReplanner(compute=compute, profile=S3_RDMA_AGG, spec=spec)
        rep.register("r0", 16384)
        n = int(16384 * 0.875) // 64
        flow = ServingSimulator(codec=codec_name).flow_request(
            WorkloadRequest("r0", 16384, 0.875))
        reduced = rep(flow, 1 * GBPS)
        assert reduced is not None
        m = reduced.bytes_per_layer * spec.num_layers / spec.wire_chunk_bytes
        assert abs(m - round(m)) < 1e-6 and 0 < round(m) < n


# ---------------------------------------------------------------------------
# cluster-sim conformance with codec-adjusted byte counts
# ---------------------------------------------------------------------------
class TestClusterConformance:
    @pytest.mark.parametrize("codec_name", ["int8", "int4", "gw8", "gw4/g64",
                                            MIXED32])
    @pytest.mark.parametrize("context,hit", [(16384, 0.875), (65536, 0.5)])
    def test_layerwise_unthrottled(self, codec_name, context, hit):
        from repro.cluster import ClusterSim, TraceRequest
        sim = ServingSimulator(codec=codec_name)
        cs = ClusterSim(cap_bps=None, codec=codec_name)
        rec = cs.run([TraceRequest("r0", 0.0, context, hit)]).records[0]
        want = sim.ttft_layerwise(WorkloadRequest("r0", context, hit)).ttft_s
        assert rec.ttft_s == pytest.approx(want, abs=1e-9)

    @pytest.mark.parametrize("codec_name", ["int8", "int4", "gw4", MIXED32])
    def test_layerwise_capped(self, codec_name):
        from repro.cluster import ClusterSim, TraceRequest
        sim = ServingSimulator(codec=codec_name)
        w = WorkloadRequest("r0", 16384, 0.875)
        cap = 10 * GBPS
        rate = allocate([sim.flow_request(w)], cap, Policy.CAL_STALL_OPT,
                        0.0)["r0"]
        cs = ClusterSim(cap_bps=cap, policy=Policy.CAL_STALL_OPT,
                        codec=codec_name)
        rec = cs.run([TraceRequest("r0", 0.0, 16384, 0.875)]).records[0]
        want = sim.ttft_layerwise(w, rate_limit=rate).ttft_s
        assert rec.ttft_s == pytest.approx(want, abs=1e-9)

    @pytest.mark.parametrize("codec_name", ["int8", "int4", MIXED32])
    def test_chunkwise(self, codec_name):
        from repro.cluster import ClusterSim, TraceRequest
        from repro.core.transport import S3_RDMA_BATCH
        sim = ServingSimulator(codec=codec_name)
        w = WorkloadRequest("r0", 16384, 0.875)
        cs = ClusterSim(cap_bps=None, profile=S3_RDMA_BATCH, mode="chunkwise",
                        codec=codec_name)
        rec = cs.run([TraceRequest("r0", 0.0, 16384, 0.875)]).records[0]
        assert rec.ttft_s == pytest.approx(sim.ttft_chunkwise(w).ttft_s,
                                           abs=1e-9)

    def test_compressed_flow_releases_pool_earlier(self):
        """Same trace, same cap: the int4 flow moves 3.76x fewer bytes, so
        its transfer must leave the shared pool sooner."""
        from repro.cluster import ClusterSim, TraceRequest
        cap = 10 * GBPS
        trace = [TraceRequest("r0", 0.0, 16384, 0.875)]
        t_raw = ClusterSim(cap_bps=cap, codec="identity").run(trace)
        t_c = ClusterSim(cap_bps=cap, codec="int4").run(trace)
        assert t_c.records[0].flow_done_s < t_raw.records[0].flow_done_s


# ---------------------------------------------------------------------------
# codec spec grammar + variable-rate sizing
# ---------------------------------------------------------------------------
class TestCodecGrammar:
    def test_defaults(self):
        assert parse_codec("gw8").group == 128 and parse_codec("gw8").bits == 8
        assert parse_codec("gw4/g32").group == 32
        fmt = parse_codec("mixed/848/g4")
        assert fmt.bit_map == (8, 4, 8) and fmt.group == 4
        assert parse_codec("mixed/48").group == 1  # per-channel default

    @pytest.mark.parametrize("bad", ["zstd", "gw8/x4", "gw8/g0", "mixed",
                                     "mixed/842", "mixed/84/g2/extra",
                                     "int8/g4"])
    def test_garbage_rejected(self, bad):
        with pytest.raises(ValueError):
            parse_codec(bad)

    def test_family_ids_stable(self):
        assert codec_wire_id("identity") == 0
        assert codec_wire_id("int8") == 1 and codec_wire_id("int4") == 2
        assert codec_wire_id("gw8/g4") == 3 and codec_wire_id("gw4") == 4
        assert codec_wire_id("mixed/84") == 5

    def test_codec_for_id_resolves_canonical_families_only(self):
        """The descriptor id names the family; parameters live in KVSpec.
        Families with a canonical default resolve to it; mixed-bit (whose
        bit map is per-deployment) is refused rather than guessed."""
        from repro.codec import codec_for_id, get_codec
        get_codec("mixed/84/g4")  # memoised — must NOT become id 5's answer
        assert codec_for_id(3).name == "gw8" and codec_for_id(3).group == 128
        assert codec_for_id(1).name == "int8"
        with pytest.raises(ValueError, match="no canonical"):
            codec_for_id(5)
        with pytest.raises(ValueError, match="unknown wire codec id"):
            codec_for_id(99)

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="does not divide"):
            _spec("gw8/g3")  # width 8
        with pytest.raises(ValueError, match="entries for"):
            _spec("mixed/84")  # 2 entries, 3 layers
        with pytest.raises(ValueError, match="even width"):
            KVSpec(2, 4, 1, 3, 2, codec="mixed/48")  # width 3, 4-bit layer

    def test_variable_rate_sizing(self):
        spec = _spec("mixed/848/g4")
        sizes = [spec.wire_layer_bytes(l) for l in range(3)]
        assert sizes[0] == sizes[2] > sizes[1]  # 8-bit layers are bigger
        assert spec.wire_chunk_bytes == sum(sizes)
        assert spec.wire_layer_offsets == (0, sizes[0], sizes[0] + sizes[1],
                                           sum(sizes))
        assert spec.mean_wire_layer_bytes == pytest.approx(sum(sizes) / 3)
        assert spec.is_variable_rate
        with pytest.raises(ValueError, match="variable per-layer"):
            spec.wire_per_layer_chunk_bytes

    def test_uniform_mixed_map_is_constant_rate(self):
        spec = _spec("mixed/888/g4")
        assert not spec.is_variable_rate
        assert spec.wire_per_layer_chunk_bytes == spec.wire_layer_bytes(1)

    def test_groupwise_cuts_scale_overhead(self):
        pc, gw = _spec("int8"), _spec("gw8/g8")
        assert gw.scale_bytes_per_layer * 8 == pc.scale_bytes_per_layer
        assert gw.wire_chunk_bytes < pc.wire_chunk_bytes


# ---------------------------------------------------------------------------
# group-wise reference primitives
# ---------------------------------------------------------------------------
class TestGroupedPrimitives:
    def test_group1_equals_per_channel(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((4, 16, 8)).astype(np.float32)
        q1, s1 = cref.quantize_per_channel(x, 8)
        q2, s2 = cref.quantize_grouped(x, 8, 1)
        np.testing.assert_array_equal(q1, q2)
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(
            cref.dequantize_per_channel(q1, s1),
            cref.dequantize_grouped(q2, s2, 1))

    @pytest.mark.parametrize("bits,group", [(8, 2), (8, 4), (4, 2), (4, 8)])
    def test_grouped_error_bounded_by_half_scale(self, bits, group):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((3, 16, 8)).astype(np.float32)
        q, scales = cref.quantize_grouped(x, bits, group)
        y = cref.dequantize_grouped(q, scales, group)
        s = np.repeat(scales.astype(np.float32), group, axis=-1)[..., None, :]
        assert np.all(np.abs(y - x) <= 0.51 * s + 1e-7)

    def test_grouped_scale_is_group_absmax(self):
        x = np.zeros((1, 4, 8), np.float32)
        x[0, 2, 5] = 7.0  # lives in group 1 of 2 (channels 4..7)
        _, scales = cref.quantize_grouped(x, 8, 4)
        assert scales.shape == (1, 2)
        assert float(scales[0, 1]) == pytest.approx(7.0 / 127, rel=1e-3)
        assert float(scales[0, 0]) == 0.0

    def test_indivisible_group_rejected(self):
        with pytest.raises(ValueError, match="does not divide"):
            cref.quantize_grouped(np.zeros((2, 4, 6), np.float32), 8, 4)


# ---------------------------------------------------------------------------
# property-based: round-trip, exact sizing, bijectivity — every codec family
# ---------------------------------------------------------------------------
class TestCodecProperties:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 4), st.sampled_from([2, 4, 8]),
           st.sampled_from([1, 2, 4]), st.integers(0, 5), st.integers(0, 10**6))
    def test_roundtrip_and_exact_sizing(self, L, G, group, codec_i, seed):
        """For random shapes, group sizes and bit maps: encode→decode error
        stays under the half-scale bound, and the wire-size accounting is
        exact — sum(wire_layer_bytes) == len(encoded) == wire_chunk_bytes."""
        rng = np.random.default_rng(seed)
        names = ["identity", "int8", "int4", f"gw8/g{group}", f"gw4/g{group}",
                 mixed_codec_name([rng.choice([4, 8]) for _ in range(L)],
                                  group)]
        name = names[codec_i]
        spec = KVSpec(num_layers=L, chunk_tokens=G, num_kv_heads=2, head_dim=4,
                      dtype_bytes=2, codec=name)
        codec = get_codec(name)
        import ml_dtypes
        k = rng.standard_normal((L, G, 8)).astype(ml_dtypes.bfloat16)
        v = rng.standard_normal((L, G, 8)).astype(ml_dtypes.bfloat16)
        buf = codec.encode_chunk(k, v, spec)
        assert len(buf) == spec.wire_chunk_bytes
        assert len(buf) == sum(spec.wire_layer_bytes(l) for l in range(L))
        for l in range(L):
            lo, hi = layer_range(l, spec)
            bits = codec.layer_bits(spec, l)
            dt = ml_dtypes.bfloat16 if codec.lossless else np.float32
            kk, vv = codec.decode_layer_payload(buf[lo:hi], 1, spec, dt,
                                                layer=l)
            for got, x in ((kk, k[l]), (vv, v[l])):
                x = np.asarray(x, np.float32)
                got = np.asarray(got, np.float32)
                if codec.lossless:
                    np.testing.assert_array_equal(got, x)
                else:
                    qmax = cref.qmax_for_bits(bits)
                    bound = 0.51 * np.abs(x).max() / qmax + 1e-6
                    assert np.abs(got - x).max() <= bound

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 5), st.integers(1, 8), st.integers(0, 10**6))
    def test_pack_unpack_int4_bijective(self, rows, half_width, seed):
        rng = np.random.default_rng(seed)
        q = rng.integers(-8, 8, size=(rows, 2 * half_width)).astype(np.int8)
        packed = cref.pack_int4(q)
        assert packed.shape == (rows, half_width)  # exactly half the bytes
        np.testing.assert_array_equal(cref.unpack_int4(packed), q)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(2, 5), st.integers(0, 10**6))
    def test_aggregated_payload_prefix_order_all_codecs(self, n_chunks, seed):
        """Decoding an N-chunk aggregated payload equals the concatenation of
        the per-chunk decodes, for every registered codec family."""
        import ml_dtypes
        rng = np.random.default_rng(seed)
        for name in ALL_FAMILY_CODECS:
            spec = _spec(name)
            codec = get_codec(name)
            bufs, ks = [], []
            for i in range(n_chunks):
                k = rng.standard_normal((3, 8, 8)).astype(ml_dtypes.bfloat16)
                v = rng.standard_normal((3, 8, 8)).astype(ml_dtypes.bfloat16)
                bufs.append(codec.encode_chunk(k, v, spec))
                ks.append(k)
            l = 1
            lo, hi = layer_range(l, spec)
            payload = b"".join(b[lo:hi] for b in bufs)
            dt = ml_dtypes.bfloat16 if codec.lossless else np.float32
            kk, _ = codec.decode_layer_payload(payload, n_chunks, spec, dt,
                                               layer=l)
            parts = [codec.decode_layer_payload(b[lo:hi], 1, spec, dt,
                                                layer=l)[0] for b in bufs]
            np.testing.assert_array_equal(np.asarray(kk),
                                          np.concatenate(parts))


# ---------------------------------------------------------------------------
# descriptor v3: size tables, multi-version wire, golden fixtures
# ---------------------------------------------------------------------------
class TestDescriptorV3:
    def _keys(self, n=4):
        return [bytes(range(i, i + 16)) for i in range(0, 16 * n, 16)]

    @pytest.mark.parametrize("codec_name", ALL_FAMILY_CODECS)
    def test_v3_roundtrip_every_family(self, codec_name):
        spec = _spec(codec_name)
        d = make_descriptor(self._keys(), spec, Delivery.LAYERWISE)
        d2 = Descriptor.from_wire(d.to_wire())
        assert d2 == d
        assert d2.total_bytes == spec.matched_wire_bytes(4)
        for l in range(spec.num_layers):
            assert d2.chunk_layer_bytes(0, l) == spec.wire_layer_bytes(l)
            assert d2.layer_offset(l) == spec.wire_layer_offsets[l]

    def test_variable_table_only_in_v3(self):
        spec = _spec("mixed/848/g4")
        d = make_descriptor(self._keys(), spec, Delivery.LAYERWISE)
        assert d.layer_bytes == tuple(spec.wire_layer_bytes(l)
                                      for l in range(3))
        with pytest.raises(ValueError, match="v3"):
            d.to_wire(2)
        with pytest.raises(ValueError):
            d.to_wire(1)

    def test_constant_stride_is_degenerate_table(self):
        """v2 and v3 encode the same constant-stride descriptor; decoding
        either yields identical lookups (the arithmetic property survives)."""
        spec = _spec("int4")
        d = make_descriptor(self._keys(), spec, Delivery.LAYERWISE)
        from_v2 = Descriptor.from_wire(d.to_wire(2))
        from_v3 = Descriptor.from_wire(d.to_wire(3))
        assert from_v2 == from_v3 == d
        assert len(d.to_wire(3)) == len(d.to_wire(2)) + 1  # mode byte only

    def test_mode2_per_chunk_table_decodes(self):
        import struct
        from repro.core.descriptor import _HEADER_V3
        spec = _spec("mixed/848/g4")
        d = make_descriptor(self._keys(), spec, Delivery.LAYERWISE)
        head = bytearray(d.to_wire(3)[:_HEADER_V3.size])
        head[-1] = 2  # TABLE_PER_CHUNK_LAYER
        rows = list(d.layer_bytes) * d.num_chunks
        buf = (bytes(head) + struct.pack(f"<{len(rows)}I", *rows)
               + b"".join(d.chunk_keys))
        assert Descriptor.from_wire(buf) == d
        rows[0] += 1  # heterogeneous rows are reserved, must be rejected
        buf = (bytes(head) + struct.pack(f"<{len(rows)}I", *rows)
               + b"".join(d.chunk_keys))
        with pytest.raises(ValueError, match="heterogeneous"):
            Descriptor.from_wire(buf)

    def test_overhead_accounting(self):
        spec = _spec("mixed/848/g4")
        d = make_descriptor(self._keys(), spec, Delivery.LAYERWISE)
        over = descriptor_overhead_bytes(d)
        assert over["v3"] == len(d.to_wire(3))
        assert over["v3_metadata"] == over["v3"] - 4 * 16
        assert over["v3_full_table"] > over["v3"]  # mode 1 compresses rows

    @pytest.mark.parametrize("codec_name", ["identity", "gw4/g4",
                                            "mixed/848/g4"])
    def test_layerwise_aggregation_via_size_table(self, codec_name):
        """StorageServer range-reads via the size table with zero
        codec-specific code: aggregated payloads equal the chunks' table
        slices in prefix order, whatever the per-layer strides."""
        import ml_dtypes
        spec = _spec(codec_name)
        codec = get_codec(codec_name)
        store = InMemoryStore()
        keys = chunk_keys(np.arange(3 * spec.chunk_tokens), spec.chunk_tokens)
        rng = np.random.default_rng(5)
        chunks = {}
        for key in keys:
            k = rng.standard_normal((3, 8, 8)).astype(ml_dtypes.bfloat16)
            v = rng.standard_normal((3, 8, 8)).astype(ml_dtypes.bfloat16)
            chunks[key] = codec.encode_chunk(k, v, spec)
            store.put(key, chunks[key])
        desc = make_descriptor(keys, spec, Delivery.LAYERWISE)
        lw = StorageServer(store, S3_RDMA_AGG).execute_layerwise(desc)
        cw = StorageServer(store, S3_RDMA_AGG).execute_chunkwise(desc)
        assert lw.payloads == cw.payloads
        for l, payload in enumerate(lw.payloads):
            lo, hi = layer_range(l, spec)
            assert payload == b"".join(chunks[key][lo:hi] for key in keys)
            assert lw.events[l].nbytes == len(payload)


class TestGoldenDescriptors:
    """Committed descriptor bytes must re-encode byte-exactly and decode
    across versions — future wire changes cannot silently break stored
    caches."""

    CASES = [("descriptor_v1.bin", 1), ("descriptor_v2.bin", 2),
             ("descriptor_v3_const.bin", 3), ("descriptor_v3_mixed.bin", 3)]

    @pytest.mark.parametrize("fname,version", CASES)
    def test_byte_exact_reencode(self, fname, version):
        with open(os.path.join(DATA, fname), "rb") as f:
            blob = f.read()
        d = Descriptor.from_wire(blob)
        assert d.to_wire(version) == blob

    def test_cross_version_decode_consistent(self):
        """v2 and the degenerate v3 of the same descriptor decode equal."""
        with open(os.path.join(DATA, "descriptor_v2.bin"), "rb") as f:
            d2 = Descriptor.from_wire(f.read())
        with open(os.path.join(DATA, "descriptor_v3_const.bin"), "rb") as f:
            d3 = Descriptor.from_wire(f.read())
        assert d2 == d3

    def test_fixture_contents_pinned(self):
        with open(os.path.join(DATA, "descriptor_v3_mixed.bin"), "rb") as f:
            d = Descriptor.from_wire(f.read())
        spec = KVSpec(num_layers=6, chunk_tokens=64, num_kv_heads=8,
                      head_dim=128, dtype_bytes=2, codec="mixed/884444/g128")
        assert d.codec_id == spec.codec_id == 5
        assert d.layer_bytes == tuple(spec.wire_layer_bytes(l)
                                      for l in range(6))
        assert d.num_chunks == 4 and d.delivery is Delivery.LAYERWISE

    def test_v1_decodes_as_identity(self):
        with open(os.path.join(DATA, "descriptor_v1.bin"), "rb") as f:
            d = Descriptor.from_wire(f.read())
        assert d.codec_id == 0 and d.layer_bytes == ()
        spec = KVSpec(num_layers=6, chunk_tokens=64, num_kv_heads=8,
                      head_dim=128, dtype_bytes=2)
        assert d.per_layer_chunk_bytes == spec.per_layer_chunk_bytes


# ---------------------------------------------------------------------------
# mixed-bit allocator
# ---------------------------------------------------------------------------
class TestAllocator:
    def _errors(self, L=6, seed=0):
        rng = np.random.default_rng(seed)
        k = rng.standard_normal((L, 32, 8)).astype(np.float32)
        v = rng.standard_normal((L, 32, 8)).astype(np.float32)
        return {b: layer_quant_error(k, v, b, group=4) for b in (4, 8)}

    def test_errors_decrease_with_bits(self):
        e = self._errors()
        assert np.all(e[8] < e[4])

    def test_budget_respected_and_monotone(self):
        e = self._errors()
        per = {4: 100, 8: 180}
        prev = 0
        for budget in (600, 800, 1000, 1080):
            bm = greedy_bit_map(e, per, budget)
            spent = sum(per[b] for b in bm)
            assert spent <= budget
            n8 = sum(1 for b in bm if b == 8)
            assert n8 >= prev  # more budget never downgrades a layer
            prev = n8
        assert greedy_bit_map(e, per, 6 * 180) == (8,) * 6

    def test_weights_steer_upgrades(self):
        e = self._errors()
        per = {4: 100, 8: 180}
        w = [100.0, 1.0, 1.0, 1.0, 1.0, 1.0]  # layer 0 is precious
        bm = greedy_bit_map(e, per, 100 * 5 + 180, weights=w)
        assert bm[0] == 8 and bm.count(8) == 1

    def test_impossible_budget_raises(self):
        with pytest.raises(ValueError, match="budget"):
            greedy_bit_map(self._errors(), {4: 100, 8: 180}, 599)

    def test_calibrate_produces_legal_spec(self):
        from repro.codec import calibrate_mixed_codec
        rng = np.random.default_rng(1)
        k = rng.standard_normal((4, 32, 8)).astype(np.float32)
        v = rng.standard_normal((4, 32, 8)).astype(np.float32)
        int8_chunk = _spec("int8", L=4).wire_chunk_bytes
        name = calibrate_mixed_codec(
            k, v, chunk_tokens=8, num_kv_heads=2, head_dim=4,
            budget_bytes_per_chunk=0.6 * int8_chunk, group=4,
            weights=[8.0, 4.0, 2.0, 1.0])
        spec = _spec(name, L=4)
        assert spec.wire_chunk_bytes <= 0.6 * int8_chunk
        fmt = parse_codec(name)
        # decaying sensitivity: upgraded layers are a prefix of the map
        first4 = next((i for i, b in enumerate(fmt.bit_map) if b == 4), 4)
        assert all(b == 4 for b in fmt.bit_map[first4:])
