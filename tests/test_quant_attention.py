"""Fused quantized-KV attention (DESIGN.md §Kernels).

Five layers of coverage for the quantized-resident cache path:

* kernel equality — `decode_attention_quant` / `flash_attention_quant`
  (interpret mode) vs the composed oracles built from `codec.ref`
  primitives, at 1e-6, for every registered quantized codec family;
* hot-path regressions — the ragged trailing-block decode (S not a
  multiple of ``block_s``) and the width->kernel dispatch map;
* residency accounting — packed-resident contexts-per-byte vs fp-resident,
  and the single-HBM-pass byte model for fused decode;
* engine parity — `ServingEngine(kv_resident="packed")` and
  `AsyncEngine(kv_resident="packed")` against the fp-resident engines and
  the PR-5 calibrated |dlogit| bounds;
* in-jit layer slicing — the per-layer steps index the stacked weights
  by a traced layer index: the same bits as host-sliced weights, one
  compiled program for every layer.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codec import get_codec, ref as cref
from repro.configs import get_smoke_config
from repro.core import (Delivery, Gateway, InMemoryStore, KVSpec, Policy,
                        RadixIndex, layer_range, parse_codec)
from repro.core.compute_model import PaperComputeModel
from repro.core.transport import VirtualClock
from repro.kernels import ops as kernel_ops
from repro.kernels import ref
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_quant,
                                            quant_block_s)
from repro.kernels.flash_attention import flash_attention_quant
from repro.kernels.residency import (cache_bytes, composed_decode_hbm_traffic,
                                     fused_decode_hbm_reads, residency_ratio)
from repro.models import build_model
from repro.serving import (AsyncEngine, AsyncRequest, Orchestrator,
                           ServingEngine)
from repro.serving import kv_chunks
from repro.serving.kv_chunks import (_dequant_op_for, layer_payload_to_kv,
                                     layer_payload_to_packed_kv,
                                     packed_layer_to_fp)

G = 8  # engine-level chunk tokens
# the ISSUE's fused-vs-composed bar: bit-level agreement up to fp32
# accumulation order
ATOL = 1e-6


def _rand_packed(rng, B, S, KV, dh, NC, bits, group):
    """Synthetic packed cache + scale rows in the wire layout."""
    W = KV * dh
    ng = W // group
    if bits == 4:
        q = rng.integers(0, 256, size=(B, S, KV, dh // 2), dtype=np.uint8)
    else:
        q = rng.integers(-127, 128, size=(B, S, KV, dh), dtype=np.int8)
    # realistic scale magnitude: unit-variance values quantize to scales of
    # about max/qmax, so dequantized K/V come back O(1)
    qmax = cref.qmax_for_bits(bits)
    ks = ((0.5 + rng.random((B, NC, ng))) / qmax).astype(np.float16)
    vs = ((0.5 + rng.random((B, NC, ng))) / qmax).astype(np.float16)
    return jnp.asarray(q), jnp.asarray(ks), jnp.asarray(vs)


# ---------------------------------------------------------------------------
# fused kernels vs composed oracles (synthetic wire tensors)
# ---------------------------------------------------------------------------
class TestFusedDecodeAttention:
    @pytest.mark.parametrize("bits,group", [(8, 1), (8, 32), (4, 32)])
    @pytest.mark.parametrize("B,H,KV,S,dh,G_,bs", [
        (2, 8, 4, 256, 32, 32, 256),   # GQA, block spans chunks
        (1, 4, 4, 128, 64, 32, 16),    # MHA, block inside a chunk
        (2, 4, 2, 192, 32, 64, 64),    # ragged: 192 % 64 == 0 but vary len
        (1, 6, 3, 128, 32, 32, 64),    # odd KV: int4 packs one head a block
    ])
    def test_matches_composed(self, bits, group, B, H, KV, S, dh, G_, bs):
        rng = np.random.default_rng(hash((bits, group, S, bs)) % 2**31)
        q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
        kq, ks, _ = _rand_packed(rng, B, S, KV, dh, S // G_, bits, group)
        vq, vs, _ = _rand_packed(rng, B, S, KV, dh, S // G_, bits, group)
        lengths = jnp.asarray([S] + [S - G_ // 2] * (B - 1), jnp.int32)
        out = decode_attention_quant(q, kq, vq, ks, vs, lengths, bits=bits,
                                     group=group, chunk_tokens=G_,
                                     block_s=bs, interpret=True)
        want = ref.ref_decode_attention_quant(q, kq, vq, ks, vs, lengths,
                                              bits=bits, group=group,
                                              chunk_tokens=G_)
        np.testing.assert_allclose(out, want, rtol=0, atol=ATOL)

    def test_residuals_merge_with_suffix(self):
        """m/l residuals support exact partial-softmax merging (the packed
        decode path splits attention into prefix + suffix partials)."""
        rng = np.random.default_rng(7)
        B, H, KV, S, dh, G_ = 1, 4, 2, 64, 32, 16
        q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
        kq, ks, _ = _rand_packed(rng, B, S, KV, dh, S // G_, 8, 8)
        vq, vs, _ = _rand_packed(rng, B, S, KV, dh, S // G_, 8, 8)
        lengths = jnp.asarray([S], jnp.int32)
        o, m, l = decode_attention_quant(q, kq, vq, ks, vs, lengths, bits=8,
                                         group=8, chunk_tokens=G_,
                                         return_residuals=True,
                                         interpret=True)
        want = ref.ref_decode_attention_quant(q, kq, vq, ks, vs, lengths,
                                              bits=8, group=8,
                                              chunk_tokens=G_)
        np.testing.assert_allclose(o, want, rtol=0, atol=ATOL)
        assert m.shape == (B, H) and l.shape == (B, H)
        assert bool(jnp.all(l > 0))

    def test_quant_block_s_snaps_to_chunk_grid(self):
        # whole multiples of G or divisors of G pass through; others snap
        assert quant_block_s(256, 32, 64) == 64
        assert quant_block_s(256, 32, 16) == 16
        assert quant_block_s(256, 32, 48) == 32
        assert quant_block_s(128, 32, 512) == 128


class TestFusedFlashAttention:
    @pytest.mark.parametrize("bits,group", [(8, 1), (8, 32), (4, 32)])
    @pytest.mark.parametrize("causal,q_offset", [(False, 0), (True, 64)])
    def test_matches_composed(self, bits, group, causal, q_offset):
        rng = np.random.default_rng(hash((bits, group, causal)) % 2**31)
        B, Sq, H, KV, Sk, dh, G_ = 2, 16, 8, 4, 128, 32, 32
        q = jnp.asarray(rng.standard_normal((B, Sq, H, dh)), jnp.float32)
        kq, ks, _ = _rand_packed(rng, B, Sk, KV, dh, Sk // G_, bits, group)
        vq, vs, _ = _rand_packed(rng, B, Sk, KV, dh, Sk // G_, bits, group)
        out = flash_attention_quant(q, kq, vq, ks, vs, bits=bits, group=group,
                                    chunk_tokens=G_, causal=causal,
                                    q_offset=q_offset, block_q=8, block_k=64,
                                    interpret=True)
        want = ref.ref_flash_attention_quant(q, kq, vq, ks, vs, bits=bits,
                                             group=group, chunk_tokens=G_,
                                             causal=causal,
                                             q_offset=q_offset)
        np.testing.assert_allclose(out, want, rtol=0, atol=ATOL)


class TestWirePayloadEquality:
    """Fused attention over *real* wire bytes: every registered quantized
    codec family (uniform, group-wise, mixed-bit with per-layer groups),
    payloads round-tripped through encode_chunk/parse_layer_payload."""

    CODECS = ["int8", "gw8/g32", "gw4/g32", "mixed/88844444/g32"]

    @pytest.mark.parametrize("codec_name", CODECS)
    def test_decode_and_prefill_shapes(self, codec_name):
        fmt = parse_codec(codec_name)
        L = len(fmt.bit_map) if fmt.bit_map is not None else 2
        KV, dh, G_, N = 2, 32, 8, 4
        spec = KVSpec(num_layers=L, chunk_tokens=G_, num_kv_heads=KV,
                      head_dim=dh, dtype_bytes=2, codec=codec_name)
        codec = get_codec(codec_name)
        rng = np.random.default_rng(11)
        bufs = [codec.encode_chunk(
            rng.standard_normal((L, G_, spec.width)).astype(np.float32),
            rng.standard_normal((L, G_, spec.width)).astype(np.float32),
            spec) for _ in range(N)]
        S = N * G_
        H = 4
        qd = jnp.asarray(rng.standard_normal((1, H, dh)), jnp.float32)
        qp = jnp.asarray(rng.standard_normal((1, G_, H, dh)), jnp.float32)
        for l in range(L):
            lo, hi = layer_range(l, spec)
            payload = b"".join(b[lo:hi] for b in bufs)
            pkv = layer_payload_to_packed_kv(payload, N, spec, layer=l)
            assert pkv.bits == codec.layer_bits(spec, l)
            assert pkv.group == codec.layer_group(spec, l)
            args = dict(bits=pkv.bits, group=pkv.group, chunk_tokens=G_)
            # decode shape
            lengths = jnp.asarray([S], jnp.int32)
            out = decode_attention_quant(qd, *pkv.as_tuple(), lengths,
                                         block_s=16, interpret=True, **args)
            want = ref.ref_decode_attention_quant(qd, *pkv.as_tuple(),
                                                  lengths, **args)
            np.testing.assert_allclose(out, want, rtol=0, atol=ATOL)
            # prefill shape (suffix attending to the packed prefix)
            out = flash_attention_quant(qp, *pkv.as_tuple(), causal=True,
                                        q_offset=S, block_q=G_, block_k=16,
                                        interpret=True, **args)
            want = ref.ref_flash_attention_quant(qp, *pkv.as_tuple(),
                                                 causal=True, q_offset=S,
                                                 **args)
            np.testing.assert_allclose(out, want, rtol=0, atol=ATOL)
            # and the packed tensors dequantize to the host decode
            kh, vh = layer_payload_to_kv(payload, N, spec, jnp.float32, l)
            kd, vd = packed_layer_to_fp(pkv, jnp.float32)
            np.testing.assert_allclose(np.asarray(kd[0]), kh, rtol=0,
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(vd[0]), vh, rtol=0,
                                       atol=1e-6)


# ---------------------------------------------------------------------------
# hot-path regressions
# ---------------------------------------------------------------------------
class TestRaggedTrailingBlock:
    def test_decode_handles_ragged_s(self):
        """Regression: S % block_s != 0 used to hard-assert.  A 4096+G
        context with the default block_s=512 leaves a G-token trailing block;
        the lengths mask must cover it (interpret mode pads the out-of-bounds
        rows of the trailing block read with NaN — the mask has to *select*
        them away)."""
        rng = np.random.default_rng(3)
        B, H, KV, dh = 1, 4, 2, 16
        S = 4096 + G
        q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        lengths = jnp.asarray([S], jnp.int32)
        out = decode_attention(q, k, v, lengths, block_s=512, interpret=True)
        assert not bool(jnp.any(jnp.isnan(out)))
        want = ref.ref_decode_attention(q, k, v, lengths)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)

    def test_small_ragged_matches_ref(self):
        """Cheap shape sweep of the same fix: lengths both inside and beyond
        the last full block."""
        rng = np.random.default_rng(4)
        B, H, KV, dh, S = 2, 4, 2, 16, 40  # 40 % 16 != 0
        q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, KV, dh)), jnp.float32)
        lengths = jnp.asarray([40, 20], jnp.int32)
        out = decode_attention(q, k, v, lengths, block_s=16, interpret=True)
        want = ref.ref_decode_attention(q, k, v, lengths)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


class TestDispatch:
    def test_unknown_width_raises(self):
        with pytest.raises(ValueError, match="no dequant kernel for 2-bit"):
            _dequant_op_for(2)

    def test_known_widths_mapped(self):
        assert _dequant_op_for(8) is kernel_ops.kv_dequant_op
        assert _dequant_op_for(4) is kernel_ops.kv_dequant_packed4_op

    def test_packed_upload_rejects_lossless(self):
        spec = KVSpec(num_layers=1, chunk_tokens=4, num_kv_heads=1,
                      head_dim=4, dtype_bytes=2, codec="identity")
        with pytest.raises(ValueError, match="lossless"):
            layer_payload_to_packed_kv(b"\0" * spec.wire_per_layer_chunk_bytes,
                                       1, spec)

    def test_kernel_failure_raises(self, monkeypatch):
        """No fallback hides the device: a quantized payload decodes through
        the kernel or not at all (there is no host-dequant branch)."""
        def broken(*args, **kwargs):
            raise RuntimeError("kernel refused")
        monkeypatch.setitem(kv_chunks._DEQUANT_OPS, 8, broken)
        spec = KVSpec(num_layers=1, chunk_tokens=4, num_kv_heads=1,
                      head_dim=4, dtype_bytes=2, codec="int8")
        payload = get_codec("int8").encode_chunk(
            np.ones((1, 4, 4), np.float32), np.ones((1, 4, 4), np.float32),
            spec)
        with pytest.raises(RuntimeError, match="kernel refused"):
            kv_chunks.layer_payload_to_device_kv(payload, 1, spec,
                                                 jnp.float32)

    def test_interpret_only_on_cpu(self):
        assert kernel_ops._interpret() == (jax.default_backend() == "cpu")


class TestPerLayerScaleGroups:
    def test_grammar_roundtrip(self):
        fmt = parse_codec("mixed/84/g16,32")
        assert fmt.bit_map == (8, 4)
        assert fmt.group == 16 and fmt.group_map == (16, 32)
        assert fmt.layer_group(0) == 16 and fmt.layer_group(1) == 32

    def test_uniform_group_list_collapses(self):
        from repro.codec.mixedbit import mixed_codec_name
        assert mixed_codec_name([8, 4], [16, 16]) == "mixed/84/g16"
        assert mixed_codec_name([8, 4], [16, 32]) == "mixed/84/g16,32"

    def test_group_count_mismatch_rejected(self):
        with pytest.raises(ValueError):
            parse_codec("mixed/844/g16,32")

    def test_codec_threads_layer_group(self):
        spec = KVSpec(num_layers=2, chunk_tokens=8, num_kv_heads=2,
                      head_dim=16, dtype_bytes=2, codec="mixed/84/g16,32")
        codec = get_codec(spec.codec)
        assert codec.layer_group(spec, 0) == 16
        assert codec.layer_group(spec, 1) == 32
        assert spec.layer_scale_groups(0) == spec.width // 16
        assert spec.layer_scale_groups(1) == spec.width // 32
        # variable maps have no single per-chunk scale count
        with pytest.raises(ValueError):
            spec.scale_groups
        # wire accounting stays self-consistent: the encoded chunk is
        # exactly the sum of the per-layer wire slices
        rng = np.random.default_rng(5)
        k = rng.standard_normal((2, 8, spec.width)).astype(np.float32)
        v = rng.standard_normal((2, 8, spec.width)).astype(np.float32)
        buf = codec.encode_chunk(k, v, spec)
        assert len(buf) == sum(spec.wire_layer_bytes(l) for l in range(2))
        for l in range(2):
            kk, _ = codec.decode_layer_payload(
                buf[layer_range(l, spec)[0]:layer_range(l, spec)[1]], 1,
                spec, np.float32, layer=l)
            qmax = cref.qmax_for_bits(codec.layer_bits(spec, l))
            assert np.abs(kk - k[l]).max() < 8.0 / qmax

    def test_uniform_codec_layer_group(self):
        spec = KVSpec(num_layers=2, chunk_tokens=8, num_kv_heads=2,
                      head_dim=16, dtype_bytes=2, codec="gw8/g16")
        assert get_codec("gw8/g16").layer_group(spec, 0) == 16
        spec = KVSpec(num_layers=2, chunk_tokens=8, num_kv_heads=2,
                      head_dim=16, dtype_bytes=2, codec="int8")
        assert get_codec("int8").layer_group(spec, 1) == 1


# ---------------------------------------------------------------------------
# residency accounting (the ISSUE's acceptance numbers)
# ---------------------------------------------------------------------------
class TestResidency:
    # a representative long-context decode shape
    ARGS = dict(tokens=4096, num_kv_heads=8, head_dim=128, chunk_tokens=64,
                num_layers=32)

    def test_int8_contexts_per_byte(self):
        cb = cache_bytes(bits=8, group=64, **self.ARGS)
        assert residency_ratio(cb, peak=True) >= 2.0

    def test_int4_contexts_per_byte(self):
        cb = cache_bytes(bits=4, group=64, **self.ARGS)
        assert residency_ratio(cb, peak=True) >= 3.5
        # int4 holds the bar even steady-state (scale rows included)
        assert residency_ratio(cb, peak=False) >= 3.5

    def test_fused_decode_single_hbm_pass(self):
        """The fused kernel reads each resident cache byte exactly once; the
        composed path reads the wire bytes, writes fp, reads fp back."""
        for bits in (8, 4):
            cb = cache_bytes(bits=bits, group=64, **self.ARGS)
            reads = fused_decode_hbm_reads(cb, self.ARGS["tokens"],
                                           chunk_tokens=64, block_s=512)
            assert reads == cb.wire_resident
            assert composed_decode_hbm_traffic(cb) > 2 * reads


# ---------------------------------------------------------------------------
# engine-level packed residency
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _model_and_params():
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _mk_engine(codec, kv_resident="fp"):
    cfg, model, params = _model_and_params()
    spec = cfg.kv_spec(G, dtype_bytes=jnp.dtype(cfg.compute_dtype).itemsize,
                       codec=codec)
    store = InMemoryStore()
    orch = Orchestrator(RadixIndex(G), Gateway(store), spec, theta_bytes=0,
                        policy=Policy.CAL_STALL_OPT, min_hit_chunks=1)
    return ServingEngine(model, params, orch,
                         kv_resident=kv_resident), store


class TestPackedServingEngine:
    # the PR-5 calibrated end-to-end bounds (test_serving_engine
    # CODEC_BOUNDS): packed residency must not widen them
    CODEC_BOUNDS = [("int8", 0.02), ("gw8/g16", 0.03), ("gw4/g16", 0.4),
                    ("mixed/84/g16", 0.1)]

    @pytest.mark.parametrize("codec,bound", CODEC_BOUNDS)
    def test_packed_warm_within_calibrated_bound(self, codec, bound):
        engine, _ = _mk_engine(codec, kv_resident="packed")
        rng = np.random.default_rng(23)
        prompt = rng.integers(0, 200, size=48)
        cold = engine.submit(prompt, "cold")
        warm = engine.submit(prompt, "warm")
        assert warm.hit and warm.delivery is Delivery.LAYERWISE
        err = float(np.abs(warm.logits - cold.logits).max())
        assert 0.0 < err < bound, (codec, err)

    def test_packed_matches_fp_resident(self):
        """Residency is a memory-layout choice, not a numerics choice: the
        packed engine's warm logits match the fp engine's to fp32
        accumulation order."""
        rng = np.random.default_rng(29)
        prompt = rng.integers(0, 200, size=48)
        for codec in ("gw8/g16", "gw4/g16"):
            fp, _ = _mk_engine(codec, kv_resident="fp")
            pk, _ = _mk_engine(codec, kv_resident="packed")
            fp.submit(prompt, "cold"), pk.submit(prompt, "cold")
            wf = fp.submit(prompt, "warm")
            wp = pk.submit(prompt, "warm")
            assert wp.delivery is Delivery.LAYERWISE
            np.testing.assert_allclose(wp.logits, wf.logits, rtol=0,
                                       atol=1e-4)

    def test_packed_greedy_decode_matches_fp(self):
        rng = np.random.default_rng(31)
        prompt = rng.integers(0, 200, size=40)
        fp, _ = _mk_engine("gw8/g16", kv_resident="fp")
        pk, _ = _mk_engine("gw8/g16", kv_resident="packed")
        fp.submit(prompt, "cold"), pk.submit(prompt, "cold")
        wf = fp.submit(prompt, "warm", max_new_tokens=4)
        wp = pk.submit(prompt, "warm", max_new_tokens=4)
        assert wp.hit and len(wp.new_tokens) == 4
        assert wp.new_tokens == wf.new_tokens

    def test_packed_commit_is_suffix_only(self):
        """The packed warm serve never re-encodes the matched prefix: the
        store sees zero new objects for a repeat prompt (suffix chunks
        dedup against the cold commit)."""
        engine, store = _mk_engine("gw8/g16", kv_resident="packed")
        rng = np.random.default_rng(37)
        prompt = rng.integers(0, 200, size=48)
        engine.submit(prompt, "cold")
        puts = store.stats.puts
        warm = engine.submit(prompt, "warm")
        assert warm.hit and store.stats.puts == puts

    def test_packed_requires_quantized_codec(self):
        with pytest.raises(ValueError, match="quantized codec"):
            _mk_engine("identity", kv_resident="packed")

    def test_bad_resident_string_rejected(self):
        with pytest.raises(ValueError, match="kv_resident"):
            _mk_engine("int8", kv_resident="half")


class TestPackedAsyncEngine:
    def _mk(self, codec, kv_resident):
        cfg, model, params = _model_and_params()
        spec = cfg.kv_spec(G, dtype_bytes=jnp.dtype(
            cfg.compute_dtype).itemsize, codec=codec)
        orch = Orchestrator(RadixIndex(G), Gateway(InMemoryStore()), spec,
                            theta_bytes=0, clock=VirtualClock())
        return AsyncEngine(model, params, orch,
                           compute=PaperComputeModel(
                               num_layers=spec.num_layers),
                           kv_resident=kv_resident)

    def test_packed_matches_fp(self):
        rng = np.random.default_rng(41)
        shared = tuple(int(t) for t in rng.integers(0, 200, size=40))
        p1 = shared + tuple(int(t) for t in rng.integers(0, 200, size=8))
        p2 = shared + tuple(int(t) for t in rng.integers(0, 200, size=8))
        reqs = [AsyncRequest("a", p1, 0.0, max_new_tokens=3),
                AsyncRequest("b", p2, 0.5, max_new_tokens=3)]
        rf = self._mk("gw8/g16", "fp").serve(reqs)
        rp = self._mk("gw8/g16", "packed").serve(reqs)
        assert rp["b"].matched_tokens == 40
        assert rp["b"].delivery is Delivery.LAYERWISE
        for rid in ("a", "b"):
            np.testing.assert_allclose(rp[rid].logits, rf[rid].logits,
                                       rtol=0, atol=1e-4)
            assert rp[rid].new_tokens == rf[rid].new_tokens


# ---------------------------------------------------------------------------
# per-layer steps slice their own weights inside the jit
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _deep_model_and_params():
    """The smoke model at four layers, so every layer index is exercised."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), num_layers=4)
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(5))


class TestInJitLayerSlicing:
    N_PREFIX, N_SUFFIX = 4 * G, G

    def _inputs(self, cfg, params, seed):
        from repro.models import layers as nn
        rng = np.random.default_rng(seed)
        P, S = self.N_PREFIX, self.N_SUFFIX
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, S)))
        positions = P + jnp.arange(S)[None, :]
        x = nn.embed(params["embed"], cfg, tokens)
        return rng, x, positions

    def _packed(self, rng, cfg, bits):
        kq, ks, vs = _rand_packed(rng, 1, self.N_PREFIX, cfg.num_kv_heads,
                                  cfg.head_dim, self.N_PREFIX // G, bits, 16)
        vq, _, _ = _rand_packed(rng, 1, self.N_PREFIX, cfg.num_kv_heads,
                                cfg.head_dim, self.N_PREFIX // G, bits, 16)
        return kq, vq, ks, vs

    @pytest.mark.parametrize("resident", ["packed-int8", "packed-int4", "fp"])
    def test_every_layer_matches_the_block_on_host_sliced_weights(
            self, resident):
        """For each layer index, the runner's step (weights sliced inside
        the jit by a traced index) gives bit-identical outputs to the same
        block run on the layer's weights sliced on the host."""
        from repro.models import dense
        from repro.serving import ModelRunner
        cfg, model, params = _deep_model_and_params()
        runner = ModelRunner(model, params)
        rng, x, positions = self._inputs(cfg, params, 43)
        layers = params["layers"]
        if resident == "fp":
            shape = (1, self.N_PREFIX, cfg.num_kv_heads, cfg.head_dim)
            pk = jnp.asarray(rng.standard_normal(shape), jnp.float32)
            pv = jnp.asarray(rng.standard_normal(shape), jnp.float32)

            @jax.jit
            def want_fn(layer_p, x):
                h, seg = dense.block(layer_p, cfg, x, positions, (pk, pv))
                return h, seg[0], seg[1]

            def got_fn(l, x):
                return runner._layer(layers, l, x, pk, pv, positions)
        else:
            bits = 8 if resident == "packed-int8" else 4
            kv = self._packed(rng, cfg, bits)
            args = dict(bits=bits, group=16, chunk_tokens=G)

            @jax.jit
            def want_fn(layer_p, x):
                h, seg = dense.block_packed(layer_p, cfg, x, positions, kv,
                                            **args)
                return h, seg[0], seg[1]

            def got_fn(l, x):
                return runner._layer_packed(layers, l, x, kv, positions,
                                            **args)
        for l in range(cfg.num_layers):
            want = want_fn(jax.tree.map(lambda a: a[l], layers), x)
            got = got_fn(l, x)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            # different weights per layer: a wrong index would show
            x = got[0]

    def test_one_packed_program_serves_every_layer(self):
        """The layer index is traced, not static: stepping through every
        layer compiles exactly one packed layer program for one (bits,
        group, chunk_tokens, shape)."""
        from repro.serving import ModelRunner
        cfg, model, params = _deep_model_and_params()
        runner = ModelRunner(model, params)
        rng, x, positions = self._inputs(cfg, params, 47)
        kv = self._packed(rng, cfg, 8)
        for l in range(cfg.num_layers):
            x, _, _ = runner._layer_packed(params["layers"], l, x, kv,
                                           positions, bits=8, group=16,
                                           chunk_tokens=G)
        jax.block_until_ready(x)
        assert runner._layer_packed._cache_size() == 1
