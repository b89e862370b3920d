"""The system under test, built from a configuration file.

These are the only entry points of the program the benchmark uses; a PR
that changes one of them needs a benchmark PR first:

* ``repro.configs.get_config`` — the registry architecture a file starts
  from (``archs/<model_type>.py``'s ``program_config``);
* ``repro.models.build_model(cfg).init_params`` — traced for the parameter
  tree's shapes only (``weights.make_params`` fills it);
* ``repro.serving.engine.ModelRunner``;
* ``Orchestrator(RadixIndex, Gateway(InMemoryStore()), spec, theta_bytes=0)``;
* ``AsyncEngine(..., kv_resident=..., tracer=...)`` and ``AsyncEngine.serve``
  with ``AsyncRequest``;
* ``repro.launch.compile_cache.use_compile_cache``.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class System:
    cfg: object
    model: object
    params: object
    engine: object
    orch: object


def build(cell, params_fn, engine_sizes: dict, tracer=None) -> System:
    """Model, weights (``params_fn(model)``), store, orchestrator and the
    async engine for one cell's configuration file, whose architecture's
    module (``cell.arch``) gives the program's config
    (``program_config``)."""
    import jax.numpy as jnp

    from repro.core import Gateway, InMemoryStore, RadixIndex
    from repro.core.compute_model import PaperComputeModel
    from repro.core.transport import VirtualClock
    from repro.models import build_model
    from repro.serving import AsyncEngine, Orchestrator
    from repro.serving.engine import ModelRunner

    conf = cell.config
    serving = conf["serving"]
    cfg = cell.arch.program_config(conf)
    model = build_model(cfg)
    params = params_fn(model)
    G = int(serving["chunk_tokens"])
    spec = cfg.kv_spec(G, dtype_bytes=jnp.dtype(cfg.compute_dtype).itemsize,
                       codec=serving["codec"])
    orch = Orchestrator(RadixIndex(G), Gateway(InMemoryStore()), spec,
                        theta_bytes=0, clock=VirtualClock())
    engine = AsyncEngine(
        model, params, orch, runner=ModelRunner(model, params),
        compute=PaperComputeModel(num_layers=cfg.num_layers),
        num_slots=int(engine_sizes.get("num_slots", 1)),
        max_seq=int(engine_sizes.get("max_seq", 512)),
        kv_resident=serving["kv_resident"], tracer=tracer)
    return System(cfg, model, params, engine, orch)


def request(req_id: str, tokens, arrival_s: float, max_new_tokens: int):
    from repro.serving import AsyncRequest

    return AsyncRequest(req_id, tuple(int(t) for t in tokens), arrival_s,
                        max_new_tokens=max_new_tokens)
