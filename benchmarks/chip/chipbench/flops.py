"""Operations and bytes of the work the benchmark drives, counted from
shapes.  These are what the algorithm needs, not what an implementation
happens to do: padding, recomputation and repeated reads are not counted.

``conf`` is a configuration file's contents (Hugging Face key names).  A
model's counts are its architecture's (``prefill_flops`` and
``decode_flops`` of ``archs/<model_type>.py``, ``Cell.arch``); a kernel's
are here.
"""
from __future__ import annotations


def flash_attention_quant(conf: dict, *, queries: int, keys: int, bits: int,
                          group: int, chunk_tokens: int,
                          act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one ``flash_attention_quant`` call as the
    engine makes it: ``queries`` suffix rows over a packed ``keys``-token
    prefix, non-causal, returning the output and the (m, l) residuals.
    FLOPs: QK^T and PV, 4 * H * dh per (query, key).  Bytes: each input
    and output once — queries and output in the activation dtype, packed K
    and V at ``bits`` per value, fp16 scale rows, float32 residuals."""
    H, KV, dh = (int(conf["num_attention_heads"]),
                 int(conf["num_key_value_heads"]), int(conf["head_dim"]))
    flops = 4.0 * H * dh * queries * keys
    kv = 2.0 * keys * KV * dh * bits / 8.0
    scales = 2.0 * (keys / chunk_tokens) * (KV * dh / group) * 2.0
    q_out = 2.0 * queries * H * dh * act_bytes
    residuals = 2.0 * queries * H * 4.0
    return flops, kv + scales + q_out + residuals
