"""Operations and bytes of the work the benchmark drives, counted from
shapes.  These are what the algorithm needs, not what an implementation
happens to do: padding, recomputation and repeated reads are not counted.

``conf`` is a configuration file's contents (Hugging Face key names).
"""
from __future__ import annotations


def _dims(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            int(conf["intermediate_size"]), int(conf["num_hidden_layers"]),
            int(conf["vocab_size"]))


def layer_matmul_params(conf: dict) -> int:
    """Weights one token multiplies through in one layer (attention
    projections and the SwiGLU MLP)."""
    d, H, KV, dh, ff, _, _ = _dims(conf)
    return d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * ff


def attention_flops(conf: dict, queries: int, keys_seen: float) -> float:
    """QK^T and PV over all heads: 4 * H * dh per (query, visible key)."""
    _, H, _, dh, _, _, _ = _dims(conf)
    return 4.0 * H * dh * queries * keys_seen


def prefill_flops(conf: dict, suffix: int, prefix: int) -> float:
    """A prefill of ``suffix`` new tokens after a cached ``prefix``: every
    layer's matmuls, causal attention over prefix + suffix, and the output
    head for the last token."""
    d, _, _, _, _, L, V = _dims(conf)
    keys = prefix + (suffix + 1) / 2.0           # mean keys a query sees
    per_layer = (2.0 * layer_matmul_params(conf) * suffix
                 + attention_flops(conf, suffix, keys))
    return L * per_layer + 2.0 * d * V


def decode_flops(conf: dict, context: int) -> float:
    """One decoded token that sees ``context`` positions (itself included):
    every weight once, attention over the context, the output head."""
    d, _, _, _, _, L, V = _dims(conf)
    return (L * (2.0 * layer_matmul_params(conf)
                 + attention_flops(conf, 1, context)) + 2.0 * d * V)


def flash_attention_quant(conf: dict, *, queries: int, keys: int, bits: int,
                          group: int, chunk_tokens: int,
                          act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one ``flash_attention_quant`` call as the
    engine makes it: ``queries`` suffix rows over a packed ``keys``-token
    prefix, non-causal, returning the output and the (m, l) residuals.
    Bytes: each input and output once — queries and output in the
    activation dtype, packed K and V at ``bits`` per value, fp16 scale rows,
    float32 residuals."""
    _, H, KV, dh, _, _, _ = _dims(conf)
    flops = attention_flops(conf, queries, keys)
    kv = 2.0 * keys * KV * dh * bits / 8.0
    scales = 2.0 * (keys / chunk_tokens) * (KV * dh / group) * 2.0
    q_out = 2.0 * queries * H * dh * act_bytes
    residuals = 2.0 * queries * H * 4.0
    return flops, kv + scales + q_out + residuals
