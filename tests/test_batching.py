"""ContinuousBatcher unit coverage: slot admission, EOS/length exit, the
max_seq boundary, and mid-flight slot turnover (DESIGN.md §Async-engine)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving.batching import ContinuousBatcher, SlotRequest


@functools.lru_cache(maxsize=None)
def _model_and_params():
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _prefill(model, params, prompt):
    batch = {"tokens": jnp.asarray(prompt)[None, :]}
    prefill = jax.jit(lambda p, b: model.prefill(p, b))
    lg, cache = prefill(params, batch)
    lg = np.asarray(lg[0], np.float32)[:model.cfg.vocab_size]
    return int(lg.argmax()), cache


def _mk(num_slots=2, max_seq=64, eos_id=None):
    cfg, model, params = _model_and_params()
    return ContinuousBatcher(model, params, num_slots, max_seq, eos_id=eos_id)


def _run_one(batcher, prompt, max_new_tokens, req_id="r"):
    _, model, params = _model_and_params()
    first, cache = _prefill(model, params, prompt)
    req = SlotRequest(req_id, len(prompt), max_new_tokens)
    batcher.enqueue(req, cache, first)
    batcher.drain()
    return req


class TestExitConditions:
    def test_length_exit(self):
        rng = np.random.default_rng(0)
        req = _run_one(_mk(), rng.integers(0, 200, size=16), 6)
        assert req.done and len(req.tokens_out) == 6

    def test_eos_exit(self):
        """The docstring's "leave on EOS/length" promise: decoding must stop
        the moment the sampled token equals ``eos_id``."""
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 200, size=16)
        ref = _run_one(_mk(), prompt, 8)
        assert len(ref.tokens_out) == 8
        eos = ref.tokens_out[2]  # greedy decode is deterministic
        req = _run_one(_mk(eos_id=eos), prompt, 8)
        assert req.done
        assert req.tokens_out == ref.tokens_out[:3]

    def test_eos_none_never_triggers(self):
        rng = np.random.default_rng(2)
        req = _run_one(_mk(eos_id=None), rng.integers(0, 200, size=16), 5)
        assert len(req.tokens_out) == 5

    def test_last_cache_slot_is_usable(self):
        """max_seq bounds the cache positions [0, max_seq); a request may
        decode until its write position reaches max_seq, so with room for k
        decode writes it emits k+1 tokens (prefill token + k).  The old
        ``pos + 1 >= max_seq`` check retired the slot one token early."""
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 200, size=16)
        k = 4
        req = _run_one(_mk(max_seq=len(prompt) + k), prompt, 100)
        assert req.done
        assert len(req.tokens_out) == k + 1
        # the token that needed the final cache slot decodes identically in
        # an unconstrained cache — the boundary write is real, not clamped
        ref = _run_one(_mk(max_seq=64), prompt, k + 1)
        assert req.tokens_out == ref.tokens_out


class TestSlotTurnover:
    def test_queued_request_enters_freed_slot(self):
        rng = np.random.default_rng(4)
        _, model, params = _model_and_params()
        b = _mk(num_slots=1)
        reqs = []
        for i, n in enumerate((3, 5)):
            prompt = rng.integers(0, 200, size=16)
            first, cache = _prefill(model, params, prompt)
            r = SlotRequest(f"r{i}", len(prompt), n)
            b.enqueue(r, cache, first)
            reqs.append(r)
        assert b.active[0] is reqs[0] and len(b.queue) == 1
        done = b.drain()
        assert [r.req_id for r in done] == ["r0", "r1"]
        assert len(reqs[0].tokens_out) == 3 and len(reqs[1].tokens_out) == 5

    def test_batched_decode_matches_solo_decode(self):
        """Two requests sharing a slot batch decode the same tokens they
        decode alone — per-slot positions isolate the KV."""
        rng = np.random.default_rng(5)
        _, model, params = _model_and_params()
        prompts = [rng.integers(0, 200, size=16) for _ in range(2)]
        solo = [_run_one(_mk(), p, 4, f"s{i}").tokens_out
                for i, p in enumerate(prompts)]
        b = _mk(num_slots=2)
        reqs = []
        for i, p in enumerate(prompts):
            first, cache = _prefill(model, params, p)
            r = SlotRequest(f"b{i}", len(p), 4)
            b.enqueue(r, cache, first)
            reqs.append(r)
        b.drain()
        assert [r.tokens_out for r in reqs] == solo


class TestWallSpans:
    def _serve(self, tracer):
        _, model, params = _model_and_params()
        b = ContinuousBatcher(model, params, 2, 64, tracer=tracer)
        rng = np.random.default_rng(6)
        reqs = []
        for i, n in enumerate((3, 5, 4)):
            prompt = rng.integers(0, 200, size=16)
            first, cache = _prefill(model, params, prompt)
            reqs.append(SlotRequest(f"r{i}", len(prompt), n))
            b.enqueue(reqs[-1], cache, first)
        b.drain()
        return b, reqs

    def test_one_decode_step_span_per_step(self):
        """Each step is one ``decode_step`` span on ``engine/wall`` listing
        the slots that got a token, in slot order; each placement is one
        ``admit`` span; the tokens are those of an untraced batcher."""
        from repro.obs import Tracer
        tracer = Tracer()
        b, reqs = self._serve(tracer)
        steps = tracer.spans("engine/wall", "decode_step")
        assert len(steps) == b.steps == 5
        # r2 takes r0's slot 0 when r0 leaves after its second step
        assert [s.args["req_ids"] for s in steps] == [
            ["r0", "r1"], ["r0", "r1"], ["r2", "r1"], ["r2", "r1"], ["r2"]]
        assert {s.args["after"] for s in steps} == {"drain"}
        assert [s.args["req_id"] for s in
                tracer.spans("engine/wall", "admit")] == ["r0", "r1", "r2"]
        assert all(a.t1 <= c.t0 for a, c in zip(steps, steps[1:]))
        _, bare = self._serve(None)
        assert [r.tokens_out for r in reqs] == [r.tokens_out for r in bare]
