"""The system under test, built from a configuration file.

These are the only entry points of the program the benchmark uses; a PR
that changes one of them needs a benchmark PR first:

* ``repro.configs.get_config`` — the registry architecture a file starts from;
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

# configuration-file key -> ModelConfig field
_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
}
_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
PROGRAM_RMS_EPS = 1e-6  # repro.models.layers.rmsnorm's fixed epsilon


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    architecture ``arch`` with every size the file states."""
    from repro.configs import get_config

    if float(conf["rms_norm_eps"]) != PROGRAM_RMS_EPS:
        raise ValueError(f"the program's RMSNorm epsilon is "
                         f"{PROGRAM_RMS_EPS}, the file states "
                         f"{conf['rms_norm_eps']}")
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's dense MLP is SwiGLU (silu)")
    fields = {f: conf[k] for k, f in _FIELDS.items()}
    dtype = _DTYPES[conf["torch_dtype"]]
    return dataclasses.replace(get_config(conf["arch"]), **fields,
                               param_dtype=dtype, compute_dtype=dtype)


@dataclasses.dataclass
class System:
    cfg: object
    model: object
    params: object
    engine: object
    orch: object


def build(conf: dict, params_fn, engine_sizes: dict, tracer=None) -> System:
    """Model, weights (``params_fn(model)``), store, orchestrator and the
    async engine for one configuration file."""
    import jax.numpy as jnp

    from repro.core import Gateway, InMemoryStore, RadixIndex
    from repro.core.compute_model import PaperComputeModel
    from repro.core.transport import VirtualClock
    from repro.models import build_model
    from repro.serving import AsyncEngine, Orchestrator
    from repro.serving.engine import ModelRunner

    serving = conf["serving"]
    cfg = model_config(conf)
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
