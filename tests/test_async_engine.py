"""AsyncEngine conformance suite (DESIGN.md §Async-engine).

`cluster.sim.ClusterSim` is the oracle: the async engine serves real
requests (real bytes, real jitted compute) on the same fluid virtual
timeline the simulator integrates, so on a matching replay trace the
per-request admit / flow-done / prefill-done times must agree to float
precision, the span vocabulary must support one `attribute_trace` pass
over either trace, and the logits must be bit-identical to the sequential
`ServingEngine` serving the same prompts.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import Gateway, InMemoryStore, Policy, RadixIndex
from repro.core.compute_model import PaperComputeModel
from repro.core.scheduler import BandwidthPool
from repro.core.transport import S3_RDMA_AGG, VirtualClock
from repro.cluster import ClusterSim, TraceRequest, load_trace
from repro.models import build_model
from repro.obs import Tracer
from repro.obs.attribution import attribute_trace, check_identity
from repro.serving import (AsyncEngine, AsyncRequest, Orchestrator,
                           ServingEngine)

G = 8
DATA = os.path.join(os.path.dirname(__file__), "data")


@functools.lru_cache(maxsize=None)
def _model_and_params():
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _shared_runner():
    from repro.serving import ModelRunner
    _, model, params = _model_and_params()
    return ModelRunner(model, params)


def _spec():
    cfg, _, _ = _model_and_params()
    return cfg.kv_spec(G, dtype_bytes=jnp.dtype(cfg.compute_dtype).itemsize,
                       codec="identity")


def _compute():
    return PaperComputeModel(num_layers=_spec().num_layers)


def _cap(n_chunks: int, context: int) -> float:
    """A cap that forces genuine water-fill contention between two such
    flows (2x one flow's zero-stall rate, so 3+ tenants contend)."""
    spec, compute = _spec(), _compute()
    c = compute.layer_compute_s(context, n_chunks * G / context)
    return 2.0 * n_chunks * spec.mean_wire_layer_bytes / c


def _mk_stack(cap_bps=None, theta=0, max_flows=None, tracer=None,
              monitor=None, slo=None):
    """(seq_engine, async_engine, tracer) sharing one orchestrator."""
    cfg, model, params = _model_and_params()
    tracer = tracer if tracer is not None else Tracer()
    pool = None
    if cap_bps is not None:
        pool = BandwidthPool(cap_bps, Policy.CAL_STALL_OPT)
        pool.tracer = tracer
    orch = Orchestrator(RadixIndex(G), Gateway(InMemoryStore()), _spec(),
                        theta_bytes=theta, pool=pool, clock=VirtualClock(),
                        tracer=tracer)
    seq = ServingEngine(model, params, orch, runner=_shared_runner())
    eng = AsyncEngine(model, params, orch, compute=_compute(),
                      profile=S3_RDMA_AGG, session_setup=True,
                      max_flows=max_flows, runner=_shared_runner(),
                      tracer=tracer, monitor=monitor, slo=slo)
    return seq, eng, tracer


def _warm_and_prompts(seq, n, warm_chunks=4, extra=None, seed=0):
    """Warm ``n`` distinct prefixes through the sequential engine and return
    prompts extending each by ``extra`` suffix tokens (so the async match is
    exactly ``warm_chunks`` chunks, no trim ambiguity)."""
    extra = G // 2 if extra is None else extra
    rng = np.random.default_rng(seed)
    warm = [rng.integers(0, 200, size=warm_chunks * G) for _ in range(n)]
    for i, w in enumerate(warm):
        seq.submit(w, req_id=f"warm{i}")
    return [np.concatenate([w, rng.integers(0, 200, size=extra)])
            for w in warm]


def _sim_for(eng, trace, cap_bps=None, mode="layerwise", max_flows=None):
    tr = Tracer()
    sim = ClusterSim(cap_bps=cap_bps, policy=Policy.CAL_STALL_OPT,
                     compute=_compute(), profile=S3_RDMA_AGG, spec=_spec(),
                     mode=mode, session_setup=True, max_flows=max_flows,
                     tracer=tr)
    return sim.run(trace), tr


def _assert_records_match(results, sim_records, tol=1e-9):
    for rid, rec in sim_records.items():
        e = results[rid].record
        assert e.admit_s == pytest.approx(rec.admit_s, rel=tol, abs=tol)
        assert e.flow_done_s == pytest.approx(rec.flow_done_s, rel=tol,
                                              abs=tol)
        assert e.prefill_done_s == pytest.approx(rec.prefill_done_s, rel=tol,
                                                 abs=tol)
        assert e.ttft_s == pytest.approx(rec.ttft_s, rel=tol, abs=tol)


class TestClusterSimConformance:
    def test_layerwise_ttft_matches_sim(self):
        """Four staggered warm requests sharing a contended pool: the engine
        and the oracle agree per request at float precision, with >= 2
        fetches concurrently in flight."""
        n, ctx = 4, 4 * G + G // 2
        seq, eng, tracer = _mk_stack(cap_bps=_cap(4, ctx))
        prompts = _warm_and_prompts(seq, n)
        reqs = [AsyncRequest(f"r{i}", tuple(map(int, p)), arrival_s=0.002 * i)
                for i, p in enumerate(prompts)]
        results = eng.serve(reqs)
        assert eng.peak_transfers >= 2
        trace = [TraceRequest(f"r{i}", 0.002 * i, len(prompts[i]),
                              4 * G / len(prompts[i]), chunk_tokens=G)
                 for i in range(n)]
        res, _ = _sim_for(eng, trace, cap_bps=_cap(4, ctx))
        _assert_records_match(results, res.by_id())

    def test_mixed_recompute_and_queueing_matches_sim(self):
        """max_flows=1 queues arrivals; a cold request rides along as a
        recompute flight (zero wire bytes).  Admission order, queue spans and
        completion times all mirror the oracle."""
        n, ctx = 2, 4 * G + G // 2
        seq, eng, tracer = _mk_stack(cap_bps=_cap(4, ctx), max_flows=1)
        prompts = _warm_and_prompts(seq, n)
        rng = np.random.default_rng(99)
        cold = rng.integers(200, 250, size=ctx)  # disjoint alphabet: no hit
        reqs = [AsyncRequest("r0", tuple(map(int, prompts[0])), 0.0),
                AsyncRequest("r1", tuple(map(int, prompts[1])), 0.001),
                AsyncRequest("rc", tuple(map(int, cold)), 0.002)]
        results = eng.serve(reqs)
        trace = [TraceRequest("r0", 0.0, ctx, 4 * G / ctx, chunk_tokens=G),
                 TraceRequest("r1", 0.001, ctx, 4 * G / ctx, chunk_tokens=G),
                 TraceRequest("rc", 0.002, ctx, 0.0, chunk_tokens=G)]
        res, _ = _sim_for(eng, trace, cap_bps=_cap(4, ctx), max_flows=1)
        by = res.by_id()
        _assert_records_match(results, by)
        assert by["r1"].queue_s > 0  # the slot cap actually queued someone
        assert results["rc"].delivery is None
        assert results["rc"].record.bytes_total == 0.0

    def test_chunkwise_ttft_matches_sim(self):
        """theta = inf forces chunkwise delivery (bulk wire + suffix
        compute); the unthrottled oracle in chunkwise mode agrees."""
        n, ctx = 2, 4 * G + G // 2
        seq, eng, tracer = _mk_stack(cap_bps=None, theta=1 << 60)
        prompts = _warm_and_prompts(seq, n)
        reqs = [AsyncRequest(f"r{i}", tuple(map(int, p)), arrival_s=0.001 * i)
                for i, p in enumerate(prompts)]
        results = eng.serve(reqs)
        from repro.core import Delivery
        assert all(r.delivery is Delivery.CHUNKWISE
                   for r in results.values())
        trace = [TraceRequest(f"r{i}", 0.001 * i, ctx, 4 * G / ctx,
                              chunk_tokens=G) for i in range(n)]
        res, _ = _sim_for(eng, trace, cap_bps=None, mode="chunkwise")
        _assert_records_match(results, res.by_id())


class TestTraceConformance:
    def test_span_vocabulary_and_attribution_identity(self):
        """The engine emits the sim's span vocabulary — queue / wire / stall
        / compute / serve plus the ``"request"`` summary instant — and the
        real dequant spans on the wall track.  One `attribute_trace` pass
        works on both traces and the per-request components agree."""
        n, ctx = 3, 4 * G + G // 2
        seq, eng, tracer = _mk_stack(cap_bps=_cap(4, ctx), max_flows=2)
        prompts = _warm_and_prompts(seq, n)
        reqs = [AsyncRequest(f"r{i}", tuple(map(int, p)), arrival_s=0.001 * i)
                for i, p in enumerate(prompts)]
        eng.serve(reqs)
        spans = {s.name for s in tracer.spans()
                 if s.track.startswith("r") and "/" not in s.track}
        assert {"wire", "compute", "serve", "queue"} <= spans
        assert "stall" in spans or True  # stalls depend on contention shape
        wall = {s.name for s in tracer.spans() if s.track.endswith("/wall")}
        assert {"dequant", "compute"} <= wall
        insts = {i.name for i in tracer.instants()
                 if i.track.startswith("r") and "/" not in i.track}
        assert {"arrive", "request"} <= insts

        trace = [TraceRequest(f"r{i}", 0.001 * i, ctx, 4 * G / ctx,
                              chunk_tokens=G) for i in range(n)]
        _, sim_tr = _sim_for(eng, trace, cap_bps=_cap(4, ctx), max_flows=2)
        a_eng = {k: v for k, v in attribute_trace(tracer).items()
                 if not k.startswith("warm")}
        a_sim = attribute_trace(sim_tr)
        assert set(a_eng) == set(a_sim)
        check_identity(a_eng)
        check_identity(a_sim)
        for rid in a_sim:
            for comp in ("queue_s", "bandwidth_stall_s", "gate_stall_s",
                         "ttft_s"):
                assert getattr(a_eng[rid], comp) == pytest.approx(
                    getattr(a_sim[rid], comp), rel=1e-9, abs=1e-9), (rid, comp)

    def test_golden_async_trace(self):
        """Committed replay trace + committed expected virtual timeline: the
        engine AND the oracle must both reproduce the pinned times, so a
        regression in either shows up here."""
        trace = load_trace(os.path.join(DATA, "golden_async_trace.json"))
        with open(os.path.join(DATA, "golden_async_trace_expected.json")) as f:
            expected = json.load(f)
        cap = expected["cap_bps"]
        seq, eng, _ = _mk_stack(cap_bps=cap, max_flows=expected["max_flows"])
        rng = np.random.default_rng(expected["prompt_seed"])
        reqs = []
        for tr in trace:
            prompt = rng.integers(0, 200, size=tr.context)
            if tr.cached_tokens:
                seq.submit(prompt[:tr.cached_tokens], req_id="w" + tr.req_id)
            reqs.append(AsyncRequest(tr.req_id, tuple(map(int, prompt)),
                                     tr.arrival_s))
        results = eng.serve(reqs)
        res, _ = _sim_for(eng, trace, cap_bps=cap,
                          max_flows=expected["max_flows"])
        by = res.by_id()
        for rid, exp in expected["requests"].items():
            for src in (results[rid].record, by[rid]):
                assert src.admit_s == pytest.approx(exp["admit_s"], abs=1e-9)
                assert src.flow_done_s == pytest.approx(exp["flow_done_s"],
                                                        abs=1e-9)
                assert src.prefill_done_s == pytest.approx(
                    exp["prefill_done_s"], abs=1e-9)


class TestBitIdentity:
    def test_poisson_load_bit_identical_to_sequential(self):
        """The acceptance run: >= 8 Poisson arrivals, >= 2 concurrently
        in-flight fetches, and every request's logits (and greedy decode)
        bit-identical to the sequential engine serving the same prompt."""
        import random
        n, ctx = 8, 4 * G + G // 2
        seq, eng, _ = _mk_stack(cap_bps=_cap(4, ctx))
        prompts = _warm_and_prompts(seq, n)
        rng, t = random.Random(7), 0.0
        arrivals = []
        for _ in range(n):
            t += rng.expovariate(1.0 / 0.004)  # mean gap 4 ms << fetch time
            arrivals.append(t)
        reqs = [AsyncRequest(f"r{i}", tuple(map(int, p)), arrivals[i],
                             max_new_tokens=3)
                for i, p in enumerate(prompts)]
        results = eng.serve(reqs)
        assert len(results) == n
        assert eng.peak_transfers >= 2
        # a fresh sequential stack over the same warmed store
        seq2, _, _ = _mk_stack(cap_bps=_cap(4, ctx))
        prompts2 = _warm_and_prompts(seq2, n)
        for i, p in enumerate(prompts2):
            ref = seq2.submit(p, req_id=f"r{i}", max_new_tokens=3)
            np.testing.assert_array_equal(ref.logits, results[f"r{i}"].logits)
            assert ref.new_tokens == results[f"r{i}"].new_tokens
            assert ref.matched_tokens == results[f"r{i}"].matched_tokens

    def test_decode_runs_in_batcher_slots(self):
        """Decode goes through the continuous batcher (not per-request
        drain): slots turn over and all requests finish their budget."""
        n, ctx = 3, 4 * G + G // 2
        seq, eng, _ = _mk_stack(cap_bps=_cap(4, ctx))
        prompts = _warm_and_prompts(seq, n)
        reqs = [AsyncRequest(f"r{i}", tuple(map(int, p)), 0.001 * i,
                             max_new_tokens=4)
                for i, p in enumerate(prompts)]
        results = eng.serve(reqs)
        assert eng.batcher is not None and eng.batcher.steps > 0
        assert all(len(r.new_tokens) == 4 for r in results.values())

    def test_live_monitors_change_no_virtual_timestamp(self):
        """Zero perturbation with the live-observability half attached:
        StreamMonitor + SLOMonitor on the engine leave every virtual
        timestamp bit-identical, while still capturing per-window series,
        per-tenant labeled metrics, and SLO posture."""
        from repro.obs import SLOMonitor, SLOTarget, StreamMonitor
        n, ctx = 3, 4 * G + G // 2

        def serve(monitor=None, slo=None):
            seq, eng, _ = _mk_stack(cap_bps=_cap(4, ctx), monitor=monitor,
                                    slo=slo)
            prompts = _warm_and_prompts(seq, n)
            reqs = [AsyncRequest(f"r{i}", tuple(map(int, p)), 0.001 * i,
                                 tenant=("gold" if i == 0 else "bronze"))
                    for i, p in enumerate(prompts)]
            return eng, eng.serve(reqs)

        _, bare = serve()
        monitor = StreamMonitor(width_s=0.01)
        slo = SLOMonitor([SLOTarget(ttft_s=1e-9)], width_s=0.01)
        eng, monitored = serve(monitor=monitor, slo=slo)
        for rid in bare:
            a, b = bare[rid].record, monitored[rid].record
            assert (a.admit_s, a.flow_done_s, a.prefill_done_s) \
                == (b.admit_s, b.flow_done_s, b.prefill_done_s)  # exact
        assert monitor.series("ttft_s").total().count == n
        assert sorted(monitor.tenants("ttft_s")) == ["bronze", "gold"]
        assert slo.status()[""]["total"] == n
        assert slo.status()[""]["bad"] == n  # 1 ns target: all bad
        # per-tenant labeled histograms in the engine's registry
        assert eng.metrics.tenants("engine.ttft_model_s") \
            == ["bronze", "gold"]
        snap = eng.metrics.snapshot()["histograms"]
        assert snap["engine.ttft_model_s{tenant=gold}"]["count"] == 1
        assert snap["engine.ttft_model_s{tenant=bronze}"]["count"] == n - 1
        # unlabelled sees the async requests plus the seq warm-up submits
        # (both engines share the orchestrator's registry)
        assert snap["engine.ttft_model_s"]["count"] == 2 * n

    def test_commit_makes_later_requests_hit(self):
        """Write-behind commit in virtual event order: a cold request's
        chunks are visible to a later arrival with the same prefix."""
        ctx = 4 * G + G // 2
        seq, eng, _ = _mk_stack(cap_bps=_cap(4, ctx))
        rng = np.random.default_rng(3)
        base = rng.integers(0, 200, size=4 * G)
        p0 = np.concatenate([base, rng.integers(0, 200, size=G // 2)])
        p1 = np.concatenate([base, rng.integers(0, 200, size=G)])
        reqs = [AsyncRequest("r0", tuple(map(int, p0)), 0.0),
                AsyncRequest("r1", tuple(map(int, p1)), 10.0)]
        results = eng.serve(reqs)
        assert results["r0"].matched_tokens == 0
        assert results["r1"].matched_tokens == 4 * G


# the wall-clock vocabulary (DESIGN.md §Observability)
REQ_WALL = {"plan", "fetch", "dequant", "upload", "compute", "final",
            "commit"}
ENGINE_WALL = {"serve", "decode_step", "admit"}


def _wall_stack(tracer, resident="fp"):
    """A sequential engine to warm prefixes and an async engine, over an
    orchestrator whose only tracer is ``tracer`` (None: untraced); a
    ``"packed"`` resident prefix rides the gw8/g32 codec."""
    cfg, model, params = _model_and_params()
    spec = _spec() if resident == "fp" else cfg.kv_spec(
        G, dtype_bytes=jnp.dtype(cfg.compute_dtype).itemsize, codec="gw8/g32")
    orch = Orchestrator(RadixIndex(G), Gateway(InMemoryStore()), spec,
                        theta_bytes=0, clock=VirtualClock(), tracer=tracer)
    seq = ServingEngine(model, params, orch, runner=_shared_runner())
    eng = AsyncEngine(model, params, orch, compute=_compute(),
                      runner=_shared_runner(), num_slots=2, tracer=tracer,
                      kv_resident=resident)
    return seq, eng


def _wall_calls(tracer, n_calls=2, resident="fp"):
    """An async engine over warmed prefixes, and the requests of
    ``n_calls`` serve() calls: three warm layerwise hits and one cold
    recompute each, every request decoding three tokens over two slots."""
    seq, eng = _wall_stack(tracer, resident)
    prompts = _warm_and_prompts(seq, 3)
    cold = np.random.default_rng(9).integers(0, 200, size=3 * G + 5)
    calls = []
    for k in range(n_calls):
        reqs = [AsyncRequest(f"c{k}r{i}", tuple(map(int, p)), 0.001 * i,
                             max_new_tokens=3)
                for i, p in enumerate(prompts)]
        reqs.append(AsyncRequest(f"c{k}cold", tuple(map(int, cold)) + (k,),
                                 0.002, max_new_tokens=3))
        calls.append(reqs)
    return eng, calls


def _serve_calls(tracer, n_calls=2, resident="fp"):
    eng, calls = _wall_calls(tracer, n_calls, resident)
    return eng, [eng.serve(reqs) for reqs in calls]


class TestWallSpans:
    @pytest.mark.parametrize("resident", ["fp", "packed"])
    def test_every_wall_span_lies_in_its_call(self, resident):
        """Each span of the table is emitted (``materialize`` where a packed
        prefix enters decode); every ``<req>/wall`` span lies inside the
        ``engine/wall`` serve span of the call that carried the request;
        each layer step has its upload and compute."""
        tracer = Tracer()
        eng, results = _serve_calls(tracer, resident=resident)
        wall = [s for s in tracer.spans() if s.track.endswith("/wall")]
        assert {s.name for s in wall if s.track != "engine/wall"} == \
            REQ_WALL | ({"materialize"} if resident == "packed" else set())
        assert {s.name for s in tracer.spans("engine/wall")} == ENGINE_WALL
        serves = tracer.spans("engine/wall", "serve")
        assert [s.args["requests"] for s in serves] == [4, 4]
        L = _spec().num_layers
        for call, res in zip(serves, results):
            for rid, r in res.items():
                mine = tracer.spans(rid + "/wall")
                assert mine and all(call.t0 <= s.t0 <= s.t1 <= call.t1
                                    for s in mine)
                names = [s.name for s in mine]
                assert names.count("final") == names.count("commit") == 1
                if r.delivery is None:
                    assert "fetch" not in names
                    continue
                fetch, = tracer.spans(rid + "/wall", "fetch")
                assert fetch.args["objects"] == r.matched_tokens // G
                assert fetch.args["bytes"] > 0
                for name in ("dequant", "upload", "compute"):
                    assert sorted(s.args["layer"] for s in
                                  tracer.spans(rid + "/wall", name)) \
                        == list(range(L))
                if resident == "packed":
                    m, = tracer.spans(rid + "/wall", "materialize")
                    commit, = tracer.spans(rid + "/wall", "commit")
                    assert commit.t1 <= m.t0 and m.args["bytes"] > 0
        commits = {s.track: s.args for s in wall if s.name == "commit"}
        assert len(commits) == 8 and commits["c0cold/wall"]["chunks"] == 3
        assert all((a["chunks"] > 0) == (a["bytes"] > 0)
                   for a in commits.values())

    def test_one_decode_step_span_per_step_with_its_tokens(self):
        """One ``decode_step`` per batcher step; every token after a
        request's first is stamped by exactly one step listing it, and the
        first by the request's ``final``; one ``admit`` per request."""
        from repro.cluster.events import EventKind
        tracer = Tracer()
        eng, results = _serve_calls(tracer)
        steps = tracer.spans("engine/wall", "decode_step")
        assert len(steps) == eng.batcher.steps > 0
        assert {s.args["after"] for s in steps} <= \
            {k.name for k in EventKind} | {"drain"}
        for res in results:
            for rid, r in res.items():
                listed = sum(rid in s.args["req_ids"] for s in steps)
                assert listed == len(r.new_tokens) - 1
                assert len(tracer.spans(rid + "/wall", "final")) == 1
        admits = [s.args["req_id"] for s in
                  tracer.spans("engine/wall", "admit")]
        assert sorted(admits) == sorted(r for res in results for r in res)

    def test_tracer_changes_no_logit_and_no_token(self):
        _, traced = _serve_calls(Tracer())
        _, bare = _serve_calls(None)
        for a, b in zip(traced, bare):
            assert set(a) == set(b)
            for rid in a:
                np.testing.assert_array_equal(a[rid].logits, b[rid].logits)
                assert a[rid].new_tokens == b[rid].new_tokens

    @pytest.mark.parametrize("resident", ["fp", "packed"])
    def test_untraced_path_reads_no_clock_and_adds_no_sync(self, monkeypatch,
                                                          resident):
        """Without a tracer the served path waits once per layer step and
        once per request's final logits, as before the wall spans, and
        never reads the clock; with one, it also waits for each layer's
        upload, each packed prefix's expansion and each slot placement."""
        from repro.serving import async_engine, batching

        class NoClock:
            @staticmethod
            def perf_counter():
                raise AssertionError("clock read with tracing off")

        waits = []
        real = jax.block_until_ready

        def counted(x):
            waits.append(1)
            return real(x)

        def serve(tracer):
            eng, (reqs,) = _wall_calls(tracer, n_calls=1, resident=resident)
            waits.clear()
            with monkeypatch.context() as m:
                m.setattr(jax, "block_until_ready", counted)
                if tracer is None:
                    m.setattr(async_engine, "time", NoClock)
                    m.setattr(batching, "time", NoClock)
                return eng.serve(reqs), len(waits)

        res, untraced = serve(None)
        L = _spec().num_layers
        hits = sum(r.delivery is not None for r in res.values())
        assert untraced == hits * L + len(res)
        expanded = hits if resident == "packed" else 0
        assert serve(Tracer())[1] == hits * 2 * L + 2 * len(res) + expanded
