"""Slot-based continuous batching for decode.

A fixed-slot batch (the production pattern: decode compiles once for the slot
count) with per-slot positions: requests enter a free slot after prefill, emit
one token per engine step, and leave on EOS/length, freeing the slot for the
next queued request mid-flight — no global drain between batches.

With a tracer attached, every step and every slot placement is a wall-clock
span on the ``engine/wall`` track (DESIGN.md §Observability); without one,
the step reads no clock and waits on nothing beyond its own host read.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model


@dataclasses.dataclass
class SlotRequest:
    req_id: str
    prompt_len: int
    max_new_tokens: int
    tokens_out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ContinuousBatcher:
    """Decode across ``num_slots`` concurrent requests with one jitted step."""

    def __init__(self, model: Model, params, num_slots: int, max_seq: int,
                 eos_id: Optional[int] = None, tracer=None) -> None:
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.eos_id = eos_id  # None => no EOS convention (length-only exit)
        self.cache = model.init_cache(num_slots, max_seq)
        self.pos = np.zeros((num_slots,), np.int32)
        self.cur = np.zeros((num_slots,), np.int32)
        self.active: list[Optional[SlotRequest]] = [None] * num_slots
        self.queue: deque = deque()
        self.tracer = tracer

        def batched_decode_step(p, c, t, pos):
            return model.decode_step(p, c, t, pos)
        self._step = jax.jit(batched_decode_step)
        self.steps = 0

    # ------------------------------------------------------------------
    def enqueue(self, req: SlotRequest, slot_cache, first_token: int) -> None:
        """``slot_cache``: per-request cache from prefill ([L,2,1,S,KV,dh]
        pytree); copied into a free slot (queued if none free)."""
        self.queue.append((req, slot_cache, first_token))
        self._admit()

    def _admit(self) -> None:
        while self.queue and None in self.active:
            slot = self.active.index(None)
            req, slot_cache, first = self.queue.popleft()
            if self.tracer is not None:
                t0 = time.perf_counter()

            def place(dst, src):
                # dense-family KV caches: [L, 2, B, S, KV, dh]
                S = src.shape[3]
                return dst.at[:, :, slot, :S].set(src[:, :, 0].astype(dst.dtype))
            self.cache = jax.tree.map(place, self.cache, slot_cache)
            if self.tracer is not None:
                jax.block_until_ready(self.cache)
                self.tracer.span_at("engine/wall", "admit", t0,
                                    time.perf_counter(), cat="engine",
                                    req_id=req.req_id)
            self.pos[slot] = req.prompt_len
            self.cur[slot] = first
            req.tokens_out.append(first)
            self.active[slot] = req

    # ------------------------------------------------------------------
    def step(self, after: str = "") -> list[SlotRequest]:
        """One decode step across all occupied slots; returns finished reqs.

        ``after`` names what ran before the step (the engine's event kind);
        it only labels the step's span."""
        if not any(self.active):
            return []
        if self.tracer is not None:
            t0 = time.perf_counter()
            served = [r.req_id for r in self.active if r is not None]
        tok = jnp.asarray(self.cur[:, None], jnp.int32)
        pos = jnp.asarray(self.pos, jnp.int32)
        lg, self.cache = self._step(self.params, self.cache, tok, pos)
        lg = np.asarray(lg, np.float32)[:, :self.cfg.vocab_size]
        nxt = lg.argmax(-1).astype(np.int32)
        finished = []
        for s, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[s] += 1
            self.cur[s] = nxt[s]
            req.tokens_out.append(int(nxt[s]))
            # Exit on EOS or length.  The length bound compares the *next*
            # decode's write position against the cache: position `pos` is
            # writable while pos < max_seq, so the last cache slot
            # (max_seq - 1) stays usable — `pos + 1 >= max_seq` here would
            # retire the slot one token early.
            if (len(req.tokens_out) >= req.max_new_tokens
                    or (self.eos_id is not None and int(nxt[s]) == self.eos_id)
                    or self.pos[s] >= self.max_seq):
                req.done = True
                finished.append(req)
                self.active[s] = None
        self.steps += 1
        if self.tracer is not None:
            self.tracer.span_at("engine/wall", "decode_step", t0,
                                time.perf_counter(), cat="engine",
                                req_ids=served, after=after)
        self._admit()
        return finished

    def drain(self, max_steps: int = 10_000) -> list[SlotRequest]:
        done = []
        for _ in range(max_steps):
            done += self.step("drain")
            if not any(self.active) and not self.queue:
                break
        return done
