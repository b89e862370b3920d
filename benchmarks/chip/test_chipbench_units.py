"""CPU tests of the benchmark's yardstick: traffic, statistics, operation
and byte counts, and the profiler reduction on a recorded trace."""
import json
import math
import pathlib

import numpy as np
import pytest

from chipbench import arch, flops, stats, xtrace
from chipbench.peaks import peaks_for
from chipbench.traffic import Traffic, exponential_gaps

HERE = pathlib.Path(__file__).resolve().parent
MIXES = sorted((HERE / "traffic").glob("*.json"))
QWEN3 = arch.load("qwen3", [HERE])


def _take(traffic, n):
    it = traffic.requests()
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(mix):
    m = json.loads(mix.read_text())
    a = _take(Traffic(m, 151936, 2 ** 31 + 17), 50)
    b = _take(Traffic(m, 151936, 2 ** 31 + 17), 50)
    c = _take(Traffic(m, 151936, 5), 50)
    for x, y in zip(a, b):
        assert (x.doc, x.max_new_tokens, x.due_s) == (y.doc, y.max_new_tokens,
                                                      y.due_s)
        assert np.array_equal(x.suffix, y.suffix)
    assert any(not np.array_equal(x.suffix, z.suffix) for x, z in zip(a, c))


@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_every_seed_offers_the_same_work(mix):
    """Each block holds the same sizes and gaps in the same order; seeds
    change only the token ids."""
    m = json.loads(mix.read_text())
    a = _take(Traffic(m, 151936, 3), 2 * m["block"])
    b = _take(Traffic(m, 151936, 2 ** 31 + 5), 2 * m["block"])
    assert [(len(x.suffix), x.max_new_tokens, x.doc, x.due_s) for x in a] == \
        [(len(y.suffix), y.max_new_tokens, y.doc, y.due_s) for y in b]


@pytest.mark.parametrize("mix", MIXES, ids=lambda p: p.stem)
def test_every_block_holds_the_same_sizes(mix):
    m = json.loads(mix.read_text())
    B = m["block"]
    blocks = []
    for seed in (1, 2, 2 ** 31 + 3):
        reqs = _take(Traffic(m, 151936, seed), 3 * B)
        for k in range(3):
            blk = reqs[k * B:(k + 1) * B]
            blocks.append((sorted(len(r.suffix) for r in blk),
                           sorted(r.max_new_tokens for r in blk),
                           sorted(r.doc for r in blk)))
    assert all(b == blocks[0] for b in blocks)


def test_bucket_weights_and_rate():
    m = dict(json.loads((HERE / "traffic" / "doc-qa-4k.json").read_text()),
             suffix_tokens={"values": [64, 128, 256, 512],
                            "weights": [0.4, 0.3, 0.2, 0.1]})
    t = Traffic(m, 151936, 0)
    counts = {v: t.suffix_sizes.count(v) for v in set(t.suffix_sizes)}
    assert counts == {64: 8, 128: 6, 256: 4, 512: 2}
    gaps = exponential_gaps(4.0, 20)
    assert math.isclose(gaps.mean(), 0.25)
    assert np.all(np.diff(gaps) > 0)
    reqs = _take(t, 200)
    assert math.isclose(reqs[-1].due_s, 200 / m["rate_per_s"], rel_tol=1e-9)
    with pytest.raises(ValueError):
        Traffic(dict(m, block=7), 151936, 0)


@pytest.mark.parametrize("mix,suffix,output", [
    ("doc-qa-4k", 70, 0), ("fewshot-batch", 70, 215)])
def test_mixes_send_their_sourced_lengths(mix, suffix, output):
    """LMSYS-Chat-1M's mean user prompt (69.5 tokens) and response (214.5),
    as the mixes' ``source`` states."""
    m = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    reqs = _take(Traffic(m, 151936, 2 ** 31 + 9), 2 * m["block"])
    assert {len(r.suffix) for r in reqs} == {suffix}
    assert {r.max_new_tokens for r in reqs} == {output}
    assert "LMSYS-Chat-1M" in m["source"]


def test_nearest_rank():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 50) == 50
    assert stats.nearest_rank(xs, 70) == 70
    assert stats.nearest_rank([3.0, 1.0, 2.0], 50) == 2.0
    assert stats.nearest_rank([5.0], 90) == 5.0
    assert stats.nearest_rank(list(range(1, 12)), 90) == 10


def test_intervals():
    assert stats.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert stats.total([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert stats.clip([(0, 10)], [(2, 3), (5, 6)]) == [(2, 3), (5, 6)]
    assert stats.gaps([(1, 2), (3, 4)], [(0, 5)]) == [(0, 1), (2, 3), (4, 5)]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(requests, calls, conf=None, peaks=None, profiled=()):
    from chipbench.harness import RunRecord
    cell = type("C", (), {"config": conf, "arch": QWEN3})()
    return RunRecord(cell=cell, setup_s=12.5,
                     requests=requests, calls=calls, spans=[], peaks=peaks,
                     profiled=profiled)


def test_rates_are_all_work_over_all_time():
    reqs = [{"served": [1] * 10, "due": 0.0, "start": 0.0, "end": 4.0},
            {"served": [1] * 30, "due": 0.0, "start": 0.0, "end": 4.0},
            {"served": [1] * 20, "due": 4.0, "start": 4.0, "end": 10.0}]
    run = _run(reqs, [(0.0, 4.0, 2), (4.0, 10.0, 1)])
    assert _reader("output_tokens_per_s").read(run) == 6.0
    assert _reader("ttft_p50_ms").read(run) == 4000.0
    assert _reader("ttft_p70_ms").read(run) == 6000.0
    assert _reader("setup_s").read(run) == 12.5


CONF = {"model_type": "qwen3", "hidden_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 4, "intermediate_size": 16,
        "num_hidden_layers": 3, "vocab_size": 10}


def test_flop_counts_by_hand():
    # per layer: q 8x16, k 8x8, v 8x8, o 16x8, mlp 3 x 8x16
    assert QWEN3.layer_matmul_params(CONF) == \
        128 + 64 + 64 + 128 + 384
    # one decoded token at context 5: 3 layers x (2*768 + 4*4*4*5) + 2*8*10
    assert QWEN3.decode_flops(CONF, 5) == 3 * (1536 + 320) + 160
    # suffix of 2 after 6 cached: queries see 7 and 8 keys
    assert QWEN3.prefill_flops(CONF, 2, 6) == (
        3 * (2 * 768 * 2 + 4 * 4 * 4 * (7 + 8)) + 160)
    f, b = flops.flash_attention_quant(CONF, queries=3, keys=32, bits=4,
                                       group=4, chunk_tokens=16)
    assert f == 4 * 4 * 4 * 3 * 32
    kv = 2 * 32 * 2 * 4 * 4 / 8          # int4 K and V
    scales = 2 * 2 * (8 / 4) * 2         # 2 chunks, 2 groups, fp16, K and V
    q_out = 2 * 3 * 4 * 4 * 2            # bf16 queries in, output out
    resid = 2 * 3 * 4 * 4                # fp32 m and l
    assert b == kv + scales + q_out + resid


def test_peaks_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


def _recorded():
    data = json.loads((HERE / "data" / "trace_small.json").read_text())
    return data, xtrace.from_events(data["device_ops"], data["host"])


def test_trace_reduction_on_a_recorded_trace():
    data, tr = _recorded()
    windows = tr.annotations("chipbench.serve")
    assert windows == [(0.0, 0.012)]
    # idle share against a 1-us grid computed from the raw events
    ops = next(iter(data["device_ops"].values()))
    grid = np.zeros(12000, bool)
    for _, a, b in ops:
        grid[max(0, int(round(a * 1e6))):min(12000, int(round(b * 1e6)))] = True
    assert abs(xtrace.idle_share(tr, windows) - (1 - grid.mean())) < 2e-3
    # kernel time by name: one flash_attention_quant call
    t, n = xtrace.op_time(tr, lambda name: "flash_attention_quant_op" in name)
    assert n == 1
    assert t == pytest.approx(sum(b - a for name, a, b in ops
                                  if "flash_attention_quant_op" in name))
    assert xtrace.top_ops(tr, 1)[0][0] == \
        "jit_layer_packed_fn/flash_attention_quant_op"
    gaps = xtrace.idle_gaps(tr, windows, k=3,
                            prefer=frozenset({"kv_chunks.py", "engine.py"}))
    assert [g[0] for g in gaps][0] == \
        "$kv_chunks.py:152 layer_payload_to_packed_kv"
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0


def test_op_and_module_names():
    assert xtrace.op_name("%fusion.12 = bf16[2] fusion(x)") == "fusion"
    assert xtrace.op_name("%copy-done = bf16[2] copy-done(x)") == "copy-done"
    assert xtrace.module_name("jit_layer_packed_fn(1516)") == \
        "jit_layer_packed_fn"


def test_host_clock_readers_skip_the_profiled_calls():
    """A call that overlaps the profiler's sub-window is left out of the
    host clock's per-layer metrics: the Python tracer slows the host."""
    conf = dict(CONF, hidden_size=8)
    reqs = [{"served": [1, 2], "due": 0.0, "start": 0.5, "end": 1.0,
             "call": 0, "suffix": 2, "matched": 6},
            {"served": [1, 2], "due": 1.0, "start": 1.0, "end": 3.0,
             "call": 1, "suffix": 2, "matched": 6},
            {"served": [1, 2], "due": 2.0, "start": 3.0, "end": 4.0,
             "call": 2, "suffix": 2, "matched": 6}]
    calls = [(0.5, 1.0, 1), (1.0, 3.0, 1), (3.0, 4.0, 1)]
    peaks = {"bf16_flops_per_s": 1e3}
    quiet = _run(reqs, calls, conf, peaks, profiled=(1.5, 2.5))
    assert quiet.quiet_calls() == {0, 2}
    assert [r["call"] for r in quiet.quiet_requests()] == [0]
    assert _reader("admission_wait_p50_ms").read(quiet) == 500.0
    per_call = 100.0 * QWEN3.decode_flops(conf, 10) / 1e3
    assert _reader("decode_mfu").read(quiet) == pytest.approx(
        2 * per_call / 1.5)
    assert _reader("prefill_mfu").read(quiet) == pytest.approx(
        100.0 * 2 * QWEN3.prefill_flops(conf, 2, 6) / (1.5 * 1e3))
    whole = _run(reqs, calls, conf, peaks)
    assert whole.quiet_calls() == {0, 1, 2}
    assert _reader("admission_wait_p50_ms").read(whole) == 500.0
    assert _reader("decode_mfu").read(whole) == pytest.approx(
        3 * per_call / 3.5)


def test_trace_is_read_from_its_first_device_operation():
    """The device is recorded only some 100 ms after the trace starts: a
    call that starts before the first device operation is not profiled,
    and the traced window starts at that operation."""
    from chipbench.harness import MARK_ANNOTATION
    offset = 10.0   # profiler clock - harness clock
    calls = [(1.0, 1.3, 1), (1.3, 1.6, 1), (1.6, 1.9, 1)]
    trace = xtrace.from_events(
        {"/device:TPU:0": [("op", 1.12 + offset, 1.2 + offset),
                           ("op", 1.4 + offset, 1.5 + offset)]},
        [(MARK_ANNOTATION, 1.0 + offset, 1.0 + offset)])
    run = _run([], calls)
    run.place_trace(trace, 1.0, 2.0, 1.0)
    assert run.offset == pytest.approx(offset)
    assert run.profiled == (1.0, 2.0)
    assert run.traced == pytest.approx((1.12 + offset, 2.0 + offset))
    assert run.profiled_calls() == [1, 2]
    # no device operation recorded: the trace is taken from its start
    bare = xtrace.from_events({}, [(MARK_ANNOTATION, 11.0, 11.0)])
    run.place_trace(bare, 1.0, 2.0, 1.0)
    assert run.traced == pytest.approx((11.0, 12.0))
    assert run.profiled_calls() == [0, 1, 2]


def test_gap_checks_read_what_the_limits_bound():
    import jax.numpy as jnp
    from chipbench.harness import gap_checks
    from chipbench.reference import logit_gaps

    logits = jnp.array([[1.0, 3.0, 2.0], [0.0, 0.5, 4.0], [2.0, 1.0, 0.0]])
    gaps = logit_gaps(logits, [1, 0, 0])       # best 3, 4, 2: gaps 0, 4, 0
    assert list(gaps) == [0.0, 4.0, 0.0]
    both = gap_checks(gaps, {"max_logit_gap": 1.0, "mean_logit_gap": 0.5,
                             "sample_served_tokens": 3})
    assert both == {"max_logit_gap": {"value": 4.0, "limit": 1.0},
                    "mean_logit_gap": {"value": pytest.approx(4.0 / 3),
                                       "limit": 0.5}}
    assert list(gap_checks(gaps, {"mean_logit_gap": 0.5})) == \
        ["mean_logit_gap"]
    assert gap_checks(gaps[:0], {"max_logit_gap": 1.0}) == \
        {"max_logit_gap": {"value": 0.0, "limit": 1.0}}
