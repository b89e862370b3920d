"""Serving engine with ObjectCache layerwise prefill.

The paper's execution pattern (§4.2): the inference framework waits for
layer-ready notifications and proceeds as soon as the next layer's KV has
arrived.  Here prefill runs *per layer* (one jitted layer step per model
layer) so the engine can consume the storage server's layer events exactly
like vLLM+LMCache consume NIXL notifications.

Two timelines are tracked and composed with the Eq. 3 pipeline:
  * transfer: the calibrated transport model's layer-ready times (the 100 Gbps
    target cluster), from core.aggregation;
  * compute: REAL wall-clock of the JAX layer steps on this host.
Bytes are real end-to-end: KV leaves prefill as KV_L2TD objects, round-trips
the object store, and re-enters attention as prefix KV — tests assert the
logits are bit-for-bit equal to a no-cache prefill.

Families: dense/vlm/moe(homogeneous) stream layerwise; ssm/hybrid reuse
fixed-size state snapshots (fused path; see DESIGN.md §Arch-applicability);
llama4-style alternating MoE uses the fused path as well.

When the orchestrator carries a compute-or-load planner, `_serve_hybrid`
fetches only the planner's fetch-span and recomputes the rest with the suffix
(DESIGN.md §Compute-or-load).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Delivery
from repro.core.hashing import chunk_keys
from repro.core.overlap import per_layer_stalls, pipeline_ttft
from repro.hybrid.executor import HybridPlan, fetch_span_plan
from repro.models import Model
from repro.models import dense, moe
from repro.models import layers as nn
from repro.obs.metrics import MetricsRegistry

from repro.codec import get_codec

from .kv_chunks import (cache_to_chunks, layer_payload_to_device_kv,
                        layer_payload_to_kv, layer_payload_to_packed_kv)
from .orchestrator import Orchestrator


@dataclasses.dataclass
class RequestResult:
    req_id: str
    logits: np.ndarray  # last-token logits [V]
    new_tokens: list[int]
    matched_tokens: int
    delivery: Optional[Delivery]
    ttft_model_s: float  # Eq. 3-composed TTFT (transfer model + real compute)
    compute_s: float  # real wall compute
    transfer_completion_s: float
    stalls_s: list[float]

    @property
    def hit(self) -> bool:
        return self.matched_tokens > 0


_ENGINE_FIELDS = ("requests", "prefix_tokens_reused", "tokens_computed",
                  "commits")


def EngineStats(registry: Optional[MetricsRegistry] = None):
    """Engine counters as a registry-backed `obs.metrics.StatGroup`.

    Historically a plain dataclass; every field is now a locked counter in a
    `MetricsRegistry`, multi-field updates go through one atomic
    :meth:`StatGroup.add`, and ``snapshot()`` is a consistent cut (mirrors
    `StoreStats`).  Attribute access (``stats.requests``) is unchanged.
    """
    return (registry or MetricsRegistry()).group("engine", _ENGINE_FIELDS)


class ModelRunner:
    """The jitted callables of one (model, params) pair.

    Extracted from `ServingEngine` so the sequential engine and the
    continuous-batching `serving.async_engine.AsyncEngine` drive the SAME
    compiled functions — bit-identical logits across serving paths is then a
    property of the plan, not of which engine executed it.  Stateless beyond
    the compilation caches, so one runner may back any number of engines.
    """

    def __init__(self, model: Model, params) -> None:
        self.model = model
        self.params = params
        self.cfg = cfg = model.cfg

        def embed_fn(embed_p, tokens, positions):
            del positions
            return nn.embed(embed_p, cfg, tokens)

        # The per-layer steps take the stacked ``params["layers"]`` and a
        # traced layer index, and slice the layer's weights inside the jit:
        # one executable serves every layer, and the host dispatches no
        # slice or squeeze of its own.
        def layer_weights(layers, l):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
                layers)

        def layer_fn(layers, l, x, pk, pv, positions):
            layer_p = layer_weights(layers, l)
            if cfg.family == "moe":
                h, seg, _ = moe.moe_block(layer_p, cfg, x, positions, (pk, pv))
            else:
                h, seg = dense.block(layer_p, cfg, x, positions, (pk, pv))
            return h, seg[0], seg[1]

        def final_fn(params, x):
            h = nn.rmsnorm(params["final_norm"], x[:, -1:, :])
            return nn.logits(params["embed"], cfg, h)[:, 0, :]

        def layer_packed_fn(layers, l, x, packed_kv, positions, *, bits,
                            group, chunk_tokens):
            h, seg = dense.block_packed(layer_weights(layers, l), cfg, x,
                                        positions, packed_kv,
                                        bits=bits, group=group,
                                        chunk_tokens=chunk_tokens)
            return h, seg[0], seg[1]

        def decode_packed_fn(params, packed_all, sk_cache, sv_cache, token,
                             pos, *, bits_map, group_map, chunk_tokens):
            # Python-unrolled layer loop: per-layer bits/groups are static
            # (mixed-bit codecs give layers different packed dtypes/shapes),
            # which rules out a lax.scan over a stacked cache.
            x = nn.embed(params["embed"], cfg, token)
            new_k, new_v = [], []
            for l in range(cfg.num_layers):
                layer_p = jax.tree.map(lambda a: a[l], params["layers"])
                x, k_c, v_c = dense.decode_block_packed(
                    layer_p, cfg, x, packed_all[l], sk_cache[l], sv_cache[l],
                    pos, bits=bits_map[l], group=group_map[l],
                    chunk_tokens=chunk_tokens)
                new_k.append(k_c)
                new_v.append(v_c)
            x = nn.rmsnorm(params["final_norm"], x)
            lg = nn.logits(params["embed"], cfg, x)[:, 0, :]
            return lg, jnp.stack(new_k), jnp.stack(new_v)

        self._embed = jax.jit(embed_fn)
        self._layer = jax.jit(layer_fn)
        self._final = jax.jit(final_fn)
        # named, so that a device trace says which step ran
        def prefill(p, b):
            return model.prefill(p, b)

        def prefill_prefix(p, b, pk, n):
            return model.prefill(p, b, pk, n)

        def decode_step(p, c, t, pos):
            return model.decode_step(p, c, t, pos)

        self._prefill = jax.jit(prefill)
        self._prefill_prefix = jax.jit(prefill_prefix, static_argnames=("n",))
        self._decode = jax.jit(decode_step)
        self._layer_packed = jax.jit(
            layer_packed_fn, static_argnames=("bits", "group", "chunk_tokens"))
        self._decode_packed = jax.jit(
            decode_packed_fn, static_argnames=("bits_map", "group_map",
                                               "chunk_tokens"))

    def payloads_to_prefix(self, payloads, n_chunks: int, spec):
        act = jnp.dtype(self.cfg.compute_dtype)
        ks, vs = [], []
        for layer, p in enumerate(payloads):
            k, v = layer_payload_to_kv(p, n_chunks, spec, act, layer)
            ks.append(k)
            vs.append(v)
        return jnp.asarray(
            np.stack([np.stack(ks), np.stack(vs)], axis=1))[:, :, None]


class ServingEngine:
    def __init__(self, model: Model, params, orch: Orchestrator, *,
                 max_decode_len: int = 64, sync_commit: bool = True,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer=None, runner: Optional[ModelRunner] = None,
                 kv_resident: str = "fp") -> None:
        self.model = model
        self.params = params
        self.orch = orch
        self.cfg = model.cfg
        self.spec = orch.spec
        self.sync_commit = sync_commit
        self.max_decode_len = max_decode_len
        # "fp" expands fetched prefixes to model width on arrival (the
        # historical path); "packed" keeps them quantized-resident and
        # dispatches the fused dequant-attention kernels (DESIGN.md
        # §Kernels).
        if kv_resident not in ("fp", "packed"):
            raise ValueError(f"kv_resident must be 'fp' or 'packed', "
                             f"got {kv_resident!r}")
        if kv_resident == "packed":
            if get_codec(self.spec.codec).lossless:
                raise ValueError(
                    f"kv_resident='packed' needs a quantized codec, "
                    f"got {self.spec.codec!r}")
            if self.cfg.family not in ("dense", "vlm"):
                raise ValueError(
                    f"kv_resident='packed' supports dense/vlm families, "
                    f"got {self.cfg.family!r}")
            if self.cfg.logit_softcap:
                raise ValueError("kv_resident='packed' requires "
                                 "logit_softcap == 0 (fused kernels don't "
                                 "implement softcap)")
        self.kv_resident = kv_resident
        self._last_packed = None
        # one registry per serving stack: default to the orchestrator's so
        # engine + orch counters snapshot as a single consistent cut
        self.metrics = metrics if metrics is not None else orch.metrics
        self.stats = EngineStats(self.metrics)
        # wall-clock tracer (obs.trace.Tracer); shared with the orchestrator
        # unless the caller splits them.  Nullable: `if tracer is not None`
        # guards keep the uninstrumented path at one attribute test.
        self.tracer = tracer if tracer is not None else orch.tracer
        self._layerwise_ok = (self.cfg.family in ("dense", "vlm")
                              or (self.cfg.family == "moe"
                                  and self.cfg.moe_every == 1))
        # all jitted callables live on the (shareable) runner; the engine
        # keeps flat aliases so call sites read as before
        self.runner = runner if runner is not None else ModelRunner(model,
                                                                    params)
        self._embed = self.runner._embed
        self._layer = self.runner._layer
        self._final = self.runner._final
        self._prefill = self.runner._prefill
        self._prefill_prefix = self.runner._prefill_prefix
        self._decode = self.runner._decode

    # ------------------------------------------------------------------
    def submit(self, tokens: np.ndarray, req_id: str = "req",
               max_new_tokens: int = 0, layer_compute_hint_s: float = 1e-3
               ) -> RequestResult:
        """Serve one request: match -> (fetch | recompute) -> prefill ->
        greedy decode -> commit fresh chunks."""
        tokens = np.asarray(tokens, dtype=np.int32)
        # `stats.requests += 1` would be a locked read THEN a locked write —
        # two acquisitions, so concurrent submits can lose increments; add()
        # applies the delta under one acquisition
        self.stats.add(requests=1)
        if self.tracer is not None:
            with self.tracer.span(req_id, "plan", cat="engine") as a:
                plan = self.orch.plan(tokens, layer_compute_hint_s,
                                      req_id=req_id)
                a["matched_chunks"] = plan.match.num_chunks
        else:
            plan = self.orch.plan(tokens, layer_compute_hint_s, req_id=req_id)
        match = plan.match
        # the orchestrator already trimmed full-prompt matches (>= 1 suffix
        # token stays), so the plan's chunk count IS the reusable count and
        # pool demand was registered for exactly these bytes
        n_chunks = match.num_chunks
        P = n_chunks * self.spec.chunk_tokens
        use_cache = plan.delivery is not None and n_chunks > 0

        if not use_cache:
            result = self._serve_full(tokens, req_id)
        elif isinstance(plan, HybridPlan):
            if self._layerwise_ok:
                result = self._serve_hybrid(tokens, plan, n_chunks, req_id)
            else:
                # Fused families cannot overlap, but the split still governs
                # how many bytes move: fetch the fetch-span as whole chunks
                # and recompute the rest with the suffix.
                span = fetch_span_plan(plan, n_chunks, self.spec)
                m = span.match.num_chunks
                result = self._serve_chunkwise(
                    tokens, span, m, m * self.spec.chunk_tokens, req_id)
        elif plan.delivery is Delivery.LAYERWISE and self._layerwise_ok:
            result = self._serve_layerwise(tokens, plan, n_chunks, P, req_id)
        else:
            result = self._serve_chunkwise(tokens, plan, n_chunks, P, req_id)

        # the fetch is over: retire the pool flow, or every served request
        # would keep holding (and shrinking) the shared bandwidth forever
        if plan.delivery is not None:
            self.orch.release(req_id)

        # one atomic add: a concurrent snapshot must never see the reused
        # count without the computed count (the torn-snapshot invariant —
        # their sum always equals a whole number of served prompts)
        self.stats.add(prefix_tokens_reused=result.matched_tokens,
                       tokens_computed=len(tokens) - result.matched_tokens)
        self.metrics.histogram("engine.ttft_model_s").observe(
            result.ttft_model_s)
        self.metrics.histogram("engine.compute_s").observe(result.compute_s)
        if self.tracer is not None:
            self.tracer.instant(
                req_id, "served", cat="engine",
                matched_tokens=result.matched_tokens,
                delivery=(result.delivery.name if result.delivery is not None
                          else "none"),
                ttft_model_s=result.ttft_model_s,
                compute_s=result.compute_s)

        if max_new_tokens > 0:
            result.new_tokens = self._greedy_decode(
                result, tokens, max_new_tokens)
        return result

    # ------------------------------------------------------------------
    def _serve_full(self, tokens, req_id) -> RequestResult:
        batch = {"tokens": jnp.asarray(tokens)[None, :]}
        t0 = time.perf_counter()
        lg, cache = self._prefill(self.params, batch)
        lg = np.asarray(jax.block_until_ready(lg)[0], np.float32)
        dt = time.perf_counter() - t0
        if self.tracer is not None:
            self.tracer.span_at(req_id, "compute", t0, t0 + dt, cat="engine")
        self._commit(tokens, cache, req_id)
        self._last_cache = cache
        self._last_packed = None
        return RequestResult(req_id, lg, [], 0, None, dt, dt, 0.0, [])

    def _fetch(self, plan, n_chunks, req_id):
        if self.tracer is not None:
            with self.tracer.span(req_id, "fetch", cat="engine") as a:
                res = self.orch.fetch(self._trim_plan(plan, n_chunks))
                a["completion_s"] = res.completion_s
            return res
        return self.orch.fetch(self._trim_plan(plan, n_chunks))

    def _serve_chunkwise(self, tokens, plan, n_chunks, P, req_id) -> RequestResult:
        res = self._fetch(plan, n_chunks, req_id)
        prefix = self._payloads_to_prefix(res.payloads, n_chunks)
        batch = {"tokens": jnp.asarray(tokens[P:])[None, :]}
        t0 = time.perf_counter()
        lg, cache = self._prefill_prefix(self.params, batch, prefix, P)
        lg = np.asarray(jax.block_until_ready(lg)[0], np.float32)
        dt = time.perf_counter() - t0
        if self.tracer is not None:
            self.tracer.span_at(req_id, "compute", t0, t0 + dt, cat="engine")
        ttft = res.completion_s + dt  # Fig. 7a: transfer then compute
        self._commit(tokens, cache, req_id)
        self._last_cache = cache
        # chunkwise stays fp-resident: the whole prefix must be on device
        # before prefill starts anyway, so there is no residency window to
        # shrink (DESIGN.md §Kernels)
        self._last_packed = None
        return RequestResult(req_id, lg, [], P, Delivery.CHUNKWISE, ttft, dt,
                             res.completion_s, [])

    def _serve_layerwise(self, tokens, plan, n_chunks, P, req_id) -> RequestResult:
        if self.kv_resident == "packed":
            return self._serve_layerwise_packed(tokens, plan, n_chunks, P,
                                                req_id)
        cfg = self.cfg
        tracer = self.tracer
        res = self._fetch(plan, n_chunks, req_id)
        suffix = jnp.asarray(tokens[P:])[None, :]
        positions = P + jnp.arange(suffix.shape[1])[None, :]
        x = self._embed(self.params["embed"], suffix, positions)
        act = jnp.dtype(cfg.compute_dtype)
        layers = self.runner.params["layers"]
        segs_k, segs_v, compute_times = [], [], []
        for l in range(cfg.num_layers):
            # wait for the layer-ready notification (virtual transfer clock);
            # quantized payloads dequantize on device (fused Pallas kernel),
            # identity payloads are a bit view
            if tracer is not None:
                with tracer.span(req_id, "dequant", cat="engine", layer=l):
                    k_d, v_d = layer_payload_to_device_kv(
                        res.payloads[l], n_chunks, self.spec, act, layer=l)
            else:
                k_d, v_d = layer_payload_to_device_kv(
                    res.payloads[l], n_chunks, self.spec, act, layer=l)
            pk, pv = k_d[None], v_d[None]
            t0 = time.perf_counter()
            x, sk, sv = self._layer(layers, l, x, pk, pv, positions)
            x = jax.block_until_ready(x)
            dt = time.perf_counter() - t0
            compute_times.append(dt)
            if tracer is not None:
                tracer.span_at(req_id, "compute", t0, t0 + dt, cat="engine",
                               layer=l)
            segs_k.append(jnp.concatenate([pk, sk], axis=1))
            segs_v.append(jnp.concatenate([pv, sv], axis=1))
        t0 = time.perf_counter()
        lg = np.asarray(jax.block_until_ready(
            self._final(self.params, x))[0], np.float32)
        final_dt = time.perf_counter() - t0
        ready = [e.t_ready_s for e in res.events]
        ttft = pipeline_ttft(ready, compute_times) + final_dt
        stalls = per_layer_stalls(ready, compute_times)
        if tracer is not None:
            self._emit_model_timeline(req_id, ready, compute_times, final_dt)
        cache = jnp.stack([jnp.stack([k, v]) for k, v in zip(segs_k, segs_v)])
        self._commit(tokens, cache, req_id)
        self._last_cache = cache
        self._last_packed = None
        return RequestResult(req_id, lg, [], P, Delivery.LAYERWISE, ttft,
                             sum(compute_times) + final_dt, res.completion_s,
                             stalls)

    def _serve_layerwise_packed(self, tokens, plan, n_chunks, P, req_id
                                ) -> RequestResult:
        """`_serve_layerwise` with the prefix kept quantized-resident.

        Each layer's payload is uploaded as its wire image
        (`layer_payload_to_packed_kv` — packed ints + fp16 scale rows, no
        standalone dequant pass) and attention reads it through the fused
        kernels.  Only this request's suffix
        KV is ever materialized at model width, so HBM residency for the
        reused prefix is wire-sized end to end, and the suffix is all the
        engine needs to commit (prefix chunks are already content-addressed
        in the store — that's why they matched)."""
        cfg = self.cfg
        tracer = self.tracer
        res = self._fetch(plan, n_chunks, req_id)
        suffix = jnp.asarray(tokens[P:])[None, :]
        positions = P + jnp.arange(suffix.shape[1])[None, :]
        x = self._embed(self.params["embed"], suffix, positions)
        layers = self.runner.params["layers"]
        packed_layers, segs_k, segs_v, compute_times = [], [], [], []
        for l in range(cfg.num_layers):
            # same "dequant" span vocabulary as the fp path (critical-path
            # attribution keys on the name): here it times the packed upload
            if tracer is not None:
                with tracer.span(req_id, "dequant", cat="engine", layer=l,
                                 resident="packed"):
                    pkv = layer_payload_to_packed_kv(
                        res.payloads[l], n_chunks, self.spec, layer=l)
            else:
                pkv = layer_payload_to_packed_kv(
                    res.payloads[l], n_chunks, self.spec, layer=l)
            packed_layers.append(pkv)
            t0 = time.perf_counter()
            x, sk, sv = self.runner._layer_packed(
                layers, l, x, pkv.as_tuple(), positions,
                bits=pkv.bits, group=pkv.group, chunk_tokens=pkv.chunk_tokens)
            x = jax.block_until_ready(x)
            dt = time.perf_counter() - t0
            compute_times.append(dt)
            if tracer is not None:
                tracer.span_at(req_id, "compute", t0, t0 + dt, cat="engine",
                               layer=l)
            segs_k.append(sk)
            segs_v.append(sv)
        t0 = time.perf_counter()
        lg = np.asarray(jax.block_until_ready(
            self._final(self.params, x))[0], np.float32)
        final_dt = time.perf_counter() - t0
        ready = [e.t_ready_s for e in res.events]
        ttft = pipeline_ttft(ready, compute_times) + final_dt
        stalls = per_layer_stalls(ready, compute_times)
        if tracer is not None:
            self._emit_model_timeline(req_id, ready, compute_times, final_dt)
        seg_cache = jnp.stack([jnp.stack([k, v])
                               for k, v in zip(segs_k, segs_v)])
        self._commit_suffix(tokens, seg_cache, n_chunks, req_id)
        self._last_cache = None
        self._last_packed = (packed_layers, seg_cache, P)
        return RequestResult(req_id, lg, [], P, Delivery.LAYERWISE, ttft,
                             sum(compute_times) + final_dt, res.completion_s,
                             stalls)

    def _emit_model_timeline(self, req_id, ready, compute_times, final_dt):
        """The Eq. 3-composed timeline on the virtual transfer clock: layer
        l's compute starts at max(ready_l, finish_{l-1}) — the same recurrence
        `pipeline_ttft` folds, laid out as spans so the TTFT waterfall shows
        where transfer gated compute (track ``"<req>/model"``)."""
        track = req_id + "/model"
        finish = 0.0
        for l, (r, c) in enumerate(zip(ready, compute_times)):
            self.tracer.instant(track, "layer_ready", t=r, cat="model",
                                layer=l)
            start = max(r, finish)
            if l > 0 and start > finish:
                self.tracer.span_at(track, "stall", finish, start,
                                    cat="model", layer=l)
            self.tracer.span_at(track, "compute", start, start + c,
                                cat="model", layer=l)
            finish = start + c
        self.tracer.span_at(track, "final", finish, finish + final_dt,
                            cat="model")

    def _serve_hybrid(self, tokens, plan: HybridPlan, n_chunks, req_id
                      ) -> RequestResult:
        """Compute-or-load split (DESIGN.md §Compute-or-load): fetch chunks
        [0, m) layerwise while chunks [m, n) are recomputed as part of the
        suffix prefill.  The per-layer loop of `_serve_layerwise` already
        overlaps the two — each layer's recompute-span attention runs while
        later layers' payloads are still in flight — so the fetch-span rides
        it unchanged with a shorter prefix."""
        m = min(plan.fetch_chunks, n_chunks)
        if m <= 0:  # planner chose pure recompute: identical to a cache miss
            return self._serve_full(tokens, req_id)
        span = fetch_span_plan(plan, n_chunks, self.spec)
        F = m * self.spec.chunk_tokens
        result = self._serve_layerwise(tokens, span, m, F, req_id)
        result.delivery = Delivery.HYBRID
        return result

    # ------------------------------------------------------------------
    def _trim_plan(self, plan, n_chunks):
        if n_chunks == plan.match.num_chunks:
            return plan
        m = dataclasses.replace(plan.match,
                                chunk_keys=plan.match.chunk_keys[:n_chunks],
                                matched_tokens=n_chunks * self.spec.chunk_tokens)
        return dataclasses.replace(plan, match=m)

    def _payloads_to_prefix(self, payloads, n_chunks):
        return self.runner.payloads_to_prefix(payloads, n_chunks, self.spec)

    def _commit(self, tokens, cache, req_id="req"):
        if not self.sync_commit:
            return
        if self.tracer is not None:
            with self.tracer.span(req_id, "commit", cat="engine") as a:
                keys_all = chunk_keys(tokens, self.spec.chunk_tokens)
                objs = cache_to_chunks(np.asarray(cache), keys_all, self.spec)
                new = self.orch.commit(tokens, objs)
                a["new_chunks"] = len(new)
        else:
            keys_all = chunk_keys(tokens, self.spec.chunk_tokens)
            objs = cache_to_chunks(np.asarray(cache), keys_all, self.spec)
            new = self.orch.commit(tokens, objs)
        self.stats.add(commits=len(new))

    def _commit_suffix(self, tokens, seg_cache, n_prefix_chunks, req_id="req"):
        """Commit only the *suffix* chunks of a packed-resident serve.

        The prefix chunks matched, so their objects are already in the store
        under the same content-addressed keys; re-encoding them would require
        dequantizing the packed prefix just to commit bytes that exist.  The
        index insert still sees the full token stream (prefix keys resolve to
        existing entries); `orch.commit` only uploads keys present in the
        object dict, so handing it the suffix objects alone is exactly the
        dedup the store would have done."""
        if not self.sync_commit:
            return
        keys_all = chunk_keys(tokens, self.spec.chunk_tokens)
        keys_suf = keys_all[n_prefix_chunks:]
        if self.tracer is not None:
            with self.tracer.span(req_id, "commit", cat="engine") as a:
                objs = cache_to_chunks(np.asarray(seg_cache), keys_suf,
                                       self.spec)
                new = self.orch.commit(tokens, objs)
                a["new_chunks"] = len(new)
        else:
            objs = cache_to_chunks(np.asarray(seg_cache), keys_suf, self.spec)
            new = self.orch.commit(tokens, objs)
        self.stats.add(commits=len(new))

    def _greedy_decode(self, result, tokens, max_new_tokens) -> list[int]:
        if self._last_packed is not None:
            return self._greedy_decode_packed(result, tokens, max_new_tokens)
        cache = self._last_cache
        cfg = self.cfg
        S0 = len(tokens)
        room = max_new_tokens

        def grow(a):
            if a.ndim >= 4 and a.shape[3] == S0:
                pad = [(0, 0)] * a.ndim
                pad[3] = (0, room)
                return jnp.pad(a, pad)
            return a
        cache = jax.tree.map(grow, cache)
        out = []
        tok = int(np.argmax(result.logits[:cfg.vocab_size]))
        out.append(tok)
        for i in range(max_new_tokens - 1):
            pos = jnp.asarray([S0 + i], jnp.int32)
            lg, cache = self._decode(self.params, cache,
                                     jnp.asarray([[tok]], jnp.int32), pos)
            tok = int(np.argmax(np.asarray(lg[0])[:cfg.vocab_size]))
            out.append(tok)
        return out

    def _greedy_decode_packed(self, result, tokens, max_new_tokens
                              ) -> list[int]:
        """Greedy decode with the prefix still quantized-resident: every
        step's attention reads the packed prefix through the fused decode
        kernel and only the fp *suffix* cache grows."""
        packed_layers, seg_cache, P = self._last_packed
        cfg = self.cfg
        S0 = len(tokens)
        room = max_new_tokens
        # seg_cache: [L, 2, 1, S_suf, KV, dh] -> grow the suffix dim
        pad = [(0, 0)] * seg_cache.ndim
        pad[3] = (0, room)
        seg_cache = jnp.pad(seg_cache, pad)
        sk, sv = seg_cache[:, 0], seg_cache[:, 1]
        packed_all = tuple(pkv.as_tuple() for pkv in packed_layers)
        bits_map = tuple(pkv.bits for pkv in packed_layers)
        group_map = tuple(pkv.group for pkv in packed_layers)
        out = []
        tok = int(np.argmax(result.logits[:cfg.vocab_size]))
        out.append(tok)
        for i in range(max_new_tokens - 1):
            pos = jnp.asarray([S0 + i], jnp.int32)
            lg, sk, sv = self.runner._decode_packed(
                self.params, packed_all, sk, sv,
                jnp.asarray([[tok]], jnp.int32), pos, bits_map=bits_map,
                group_map=group_map, chunk_tokens=self.spec.chunk_tokens)
            tok = int(np.argmax(np.asarray(lg[0])[:cfg.vocab_size]))
            out.append(tok)
        return out
