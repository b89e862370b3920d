"""Bring-up smoke test: serve qwen3-0.6b warm layerwise hits on one TPU chip.

Drives the served path — `AsyncEngine` → orchestrator → object store →
Pallas kernels — once, through the entry points a user calls, at
qwen3-0.6b's published widths and depth (28 layers, d=1024, 16/8 heads,
head_dim 128, bf16) with random weights from a fixed seed:

1. a cold request commits a 4096-token shared prefix (gw8/g32 codec,
   16-token chunks) to an in-memory object store;
2. one `AsyncEngine(kv_resident="packed")` trace serves three warm
   layerwise hits with ragged suffixes and one cold unshared control; every
   request decodes 16 tokens in the continuous batcher;
3. one `ServingEngine(kv_resident="packed")` warm hit decodes greedily, so
   `decode_attention_quant` runs every step.

Checks, with bounds fixed before any chip run:

* the fused packed path's logits against the composed jnp reference of the
  same packed prefix (dequantize, then the model's XLA prefill);
* an identity-codec warm hit against a no-cache prefill: max |Δlogit| and
  the greedy tokens, which may part only where the reference's top-2 logit
  margin is inside the bound (a near tie);
* the compiled packed layer step and packed decode step contain Pallas
  kernels (`tpu_custom_call`).

Every line but the last is a report: compile time (set-up), wall seconds
per phase (each ends in a host read of its results, i.e. after
`block_until_ready`), each warm-trace request's wall seconds by span from
its ``<req>/wall`` track (the tracer's waits included), and the
virtual-clock TTFT, which is modelled, not measured.  The last line is
{"ok": true, "device": {...}}.  Any failed phase raises, so the script
exits non-zero without that line; it also refuses to run unless JAX's
default device is a TPU.

Run from the repository root:  python3 chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-0.6b"
SEED = 0
CHUNK_TOKENS = 16
CODEC = "gw8/g32"
PREFIX = 4096
SUFFIXES = (200, 137, 251)   # ragged: none is a multiple of a kernel block
CONTROL = 512                # unshared prompt: a cold recompute
DECODE = 16
# max |Δlogit| / max |reference logit|, fixed before the first chip run.
# On the CPU backend at full width and a 512-token prefix, bf16 rounding
# alone gave 0.9 / 1.6 / 1.8 % (packed vs composed) and 0.3 / 0.9 / 1.2 %
# (identity vs no cache) at 2 / 8 / 16 layers; the bounds leave about 3x
# over that trend at 28 layers.  An unrelated prompt differs by > 100 %.
PACKED_REL_BOUND = 8e-2
IDENTITY_REL_BOUND = 5e-2


def report(phase: str, **kv) -> None:
    fields = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{phase}] {fields}", flush=True)


class CompileClock:
    """Seconds the XLA backend spends compiling (set-up time), summed from
    JAX's own monitoring events.  Python tracing is not counted: its events
    nest (an outer jit's trace includes the inner ones)."""

    def __init__(self) -> None:
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


class Phase:
    """Wall time of one phase, with the compile time spent inside it."""

    def __init__(self, name: str, clock: CompileClock) -> None:
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self.t0
        self.compile_s = self.clock.total - self.c0
        if exc[0] is None:
            report(self.name, wall_s=self.wall_s, compile_s=self.compile_s)


def rel_err(got, ref) -> tuple[float, float]:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(ref))):
        raise AssertionError("non-finite logits")
    d = float(np.abs(got - ref).max())
    return d, d / max(float(np.abs(ref).max()), 1e-30)


def top2_margin(logits) -> float:
    a, b = np.partition(np.asarray(logits, np.float32), -2)[-2:]
    return float(b - a)


def check_tokens(got, ref, margins, tie: float) -> int:
    """Greedy tokens may part only at a near tie of the reference (top-2
    margin < ``tie``); after that they are incomparable.  Returns the number
    of leading tokens that agree."""
    for i, (g, r) in enumerate(zip(got, ref)):
        if g != r:
            if margins[i] >= tie:
                raise AssertionError(
                    f"greedy token {i}: {g} != {r} with reference top-2 "
                    f"margin {margins[i]} >= {tie}")
            return i
    if len(got) != len(ref):
        raise AssertionError(f"{len(got)} tokens != {len(ref)}")
    return len(got)


def wall_seconds(tracer, req_id: str) -> dict:
    """Seconds of each kind of wall span on the request's ``/wall`` track.
    The kinds overlap (``slice`` lies inside ``compute``), so they are
    reported apart and never summed."""
    out: dict = {}
    for s in tracer.spans(req_id + "/wall"):
        out[f"wall_{s.name}_s"] = out.get(f"wall_{s.name}_s", 0.0) + s.dur_s
    return dict(sorted(out.items()))


def count_kernels(jitted, *args, **kwargs) -> int:
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return text.count("tpu_custom_call")


def run(cfg, *, prefix: int = PREFIX, suffixes=SUFFIXES,
        control: int = CONTROL, decode: int = DECODE) -> dict:
    """The whole smoke, on whatever device JAX uses; raises on a failed
    check.  ``main`` runs it at full size on a TPU; a smaller config and
    sizes rehearse it on the CPU (where it ends at the kernel count)."""
    import jax
    import jax.numpy as jnp

    from repro.core import Delivery, Gateway, InMemoryStore, RadixIndex
    from repro.core.compute_model import PaperComputeModel
    from repro.core.hashing import chunk_keys
    from repro.core.transport import VirtualClock
    from repro.kernels.ref import ref_dequant_cache
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model
    from repro.obs import Tracer
    from repro.serving import (AsyncEngine, AsyncRequest, Orchestrator,
                               ServingEngine)
    from repro.serving.engine import ModelRunner

    report("setup", compile_cache=use_compile_cache())
    clock = CompileClock()
    rng = np.random.default_rng(SEED)
    V = cfg.vocab_size
    L = cfg.num_layers
    act = jnp.dtype(cfg.compute_dtype)

    with Phase("init_params", clock):
        model = build_model(cfg)
        params = jax.block_until_ready(
            model.init_params(jax.random.PRNGKey(SEED)))
        runner = ModelRunner(model, params)

    def orchestrator(codec_name):
        spec = cfg.kv_spec(CHUNK_TOKENS, dtype_bytes=act.itemsize,
                           codec=codec_name)
        return Orchestrator(RadixIndex(CHUNK_TOKENS),
                            Gateway(InMemoryStore()), spec, theta_bytes=0,
                            clock=VirtualClock())

    shared = rng.integers(0, V, size=prefix)

    def tail(n):
        return rng.integers(0, V, size=n)

    # -- 1 + 2: cold commit, then the warm layerwise trace ------------------
    orch = orchestrator(CODEC)
    max_seq = -(-(prefix + max(suffixes) + decode) // 512) * 512
    tracer = Tracer()
    engine = AsyncEngine(model, params, orch, runner=runner,
                         compute=PaperComputeModel(num_layers=L),
                         num_slots=len(suffixes) + 1, max_seq=max_seq,
                         kv_resident="packed", tracer=tracer)
    with Phase("cold_commit", clock):
        cold = engine.serve([AsyncRequest("cold", tuple(shared), 0.0,
                                          max_new_tokens=decode)])["cold"]
    keys = chunk_keys(shared, CHUNK_TOKENS)
    if cold.matched_tokens != 0 or not all(
            orch.gateway.store.contains(k) for k in keys):
        raise AssertionError(f"cold request did not commit {len(keys)} "
                             f"chunks")
    if len(cold.new_tokens) != decode:
        raise AssertionError(f"cold decoded {len(cold.new_tokens)} tokens")

    reqs = [AsyncRequest(f"warm{i}",
                         tuple(np.concatenate([shared, tail(n)])),
                         0.01 * i, max_new_tokens=decode)
            for i, n in enumerate(suffixes)]
    control_prompt = tail(control)
    reqs.append(AsyncRequest("control", tuple(control_prompt), 0.015,
                             max_new_tokens=decode))
    with Phase("async_warm_trace", clock):
        res = engine.serve(reqs)
    for r in reqs:
        out = res[r.req_id]
        warm = r.req_id.startswith("warm")
        want = prefix if warm else 0
        if out.matched_tokens != want or len(out.new_tokens) != decode:
            raise AssertionError(
                f"{r.req_id}: matched {out.matched_tokens} (want {want}), "
                f"{len(out.new_tokens)} tokens (want {decode})")
        if warm and out.delivery is not Delivery.LAYERWISE:
            raise AssertionError(f"{r.req_id}: delivery {out.delivery}")
        if not np.all(np.isfinite(out.logits)):
            raise AssertionError(f"{r.req_id}: non-finite logits")
        report(r.req_id, prompt=len(r.tokens), matched=out.matched_tokens,
               delivery=out.delivery.name if out.delivery else "recompute",
               ttft_modelled_s=out.ttft_s, **wall_seconds(tracer, r.req_id))

    # -- 3: packed greedy decode, then the composed reference ---------------
    seq = ServingEngine(model, params, orch, runner=runner,
                        kv_resident="packed")
    suffix = tail(suffixes[0])
    prompt = np.concatenate([shared, suffix])
    with Phase("serving_packed_decode", clock):
        served = seq.submit(prompt, "packed", max_new_tokens=decode)
    if served.matched_tokens != prefix or len(served.new_tokens) != decode:
        raise AssertionError("packed ServingEngine request was not a "
                             f"{prefix}-token hit decoding {decode} tokens")
    packed_layers, seg_cache, P = seq._last_packed

    with Phase("packed_reference", clock):
        prefix_kv = jnp.stack([jnp.stack([
            ref_dequant_cache(pkv.k_q, pkv.k_scales, bits=pkv.bits,
                              group=pkv.group, chunk_tokens=pkv.chunk_tokens),
            ref_dequant_cache(pkv.v_q, pkv.v_scales, bits=pkv.bits,
                              group=pkv.group, chunk_tokens=pkv.chunk_tokens)])
            for pkv in packed_layers]).astype(act)  # [L, 2, 1, P, KV, dh]
        ref_lg, _ = runner._prefill_prefix(
            params, {"tokens": jnp.asarray(suffix)[None]}, prefix_kv, P)
        ref_lg = np.asarray(ref_lg[0], np.float32)[:V]
    d, rel = rel_err(served.logits[:V], ref_lg)
    sep = rel_err(res["control"].logits[:V], ref_lg)[1]
    report("check_packed_vs_composed", max_abs_dlogit=d, rel=rel,
           rel_bound=PACKED_REL_BOUND, unrelated_prompt_rel=sep)
    if rel > PACKED_REL_BOUND:
        raise AssertionError(f"packed logits rel err {rel} > "
                             f"{PACKED_REL_BOUND}")
    tie = PACKED_REL_BOUND * float(np.abs(ref_lg).max())
    check_tokens([int(np.argmax(served.logits[:V]))],
                 [int(np.argmax(ref_lg))], [top2_margin(ref_lg)], tie)

    # -- identity codec: warm hit vs no-cache prefill -----------------------
    ident = ServingEngine(model, params, orchestrator("identity"),
                          runner=runner)
    suffix = tail(suffixes[0])
    prompt = np.concatenate([shared, suffix])
    with Phase("identity_warm_hit", clock):
        ident.submit(shared, "identity_cold")
        warm = ident.submit(prompt, "identity_warm", max_new_tokens=decode)
    if (warm.matched_tokens != prefix
            or warm.delivery is not Delivery.LAYERWISE):
        raise AssertionError("identity request was not a layerwise hit")
    with Phase("no_cache_reference", clock):
        lg, cache = runner._prefill(
            params, {"tokens": jnp.asarray(prompt)[None]})
        cache = jnp.pad(cache, [(0, 0)] * 3 + [(0, decode)] + [(0, 0)] * 2)
        logits = np.asarray(lg[0], np.float32)[:V]
        ref_first = logits
        ref_tokens, margins = [], []
        for i in range(decode):
            margins.append(top2_margin(logits))
            ref_tokens.append(int(np.argmax(logits)))
            if i == decode - 1:
                break
            lg, cache = runner._decode(
                params, cache, jnp.asarray([[ref_tokens[-1]]], jnp.int32),
                jnp.asarray([len(prompt) + i], jnp.int32))
            logits = np.asarray(lg[0], np.float32)[:V]
    d, rel = rel_err(warm.logits[:V], ref_first)
    tie = IDENTITY_REL_BOUND * float(np.abs(ref_first).max())
    agreed = check_tokens(warm.new_tokens, ref_tokens, margins, tie)
    report("check_identity_vs_no_cache", max_abs_dlogit=d, rel=rel,
           rel_bound=IDENTITY_REL_BOUND,
           greedy_tokens_agreed=f"{agreed}/{decode}", near_tie=tie)
    if rel > IDENTITY_REL_BOUND:
        raise AssertionError(f"identity logits rel err {rel} > "
                             f"{IDENTITY_REL_BOUND}")

    # -- the compiled hot steps run Pallas kernels --------------------------
    with Phase("kernel_count", clock):
        pkv = packed_layers[0]
        sfx = jnp.asarray(suffix)[None]
        pos = P + jnp.arange(sfx.shape[1])[None]
        x = runner._embed(params["embed"], sfx, pos)
        n_layer = count_kernels(
            runner._layer_packed, params["layers"], 0, x, pkv.as_tuple(),
            pos, bits=pkv.bits, group=pkv.group, chunk_tokens=pkv.chunk_tokens)
        sk = jnp.pad(seg_cache[:, 0],
                     [(0, 0)] * 2 + [(0, decode)] + [(0, 0)] * 2)
        n_decode = count_kernels(
            runner._decode_packed, params,
            tuple(p.as_tuple() for p in packed_layers), sk, sk,
            jnp.zeros((1, 1), jnp.int32), jnp.asarray([P], jnp.int32),
            bits_map=tuple(p.bits for p in packed_layers),
            group_map=tuple(p.group for p in packed_layers),
            chunk_tokens=pkv.chunk_tokens)
    report("tpu_custom_call", packed_layer_step=n_layer,
           packed_decode_step=n_decode)
    if n_layer <= 0 or n_decode <= 0:
        raise AssertionError("a packed hot step compiled without a kernel")
    report("setup", compile_s_total=clock.total)
    return {"compile_s": clock.total}


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.configs import get_config

    report("device", platform=dev.platform, device_kind=dev.device_kind,
           count=len(jax.devices()))
    run(get_config(ARCH))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
