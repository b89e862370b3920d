"""Dense decoder-only transformer (Qwen3 / SmolLM / Gemma / Llama families).

Layers are *stacked* along a leading L axis and executed with ``lax.scan`` —
one layer's HLO regardless of depth, which keeps multi-pod compile times sane
and is the production pattern (MaxText).  Three entry points:

  ``loss``         — training forward + cross-entropy (train_4k shape)
  ``prefill``      — full or suffix prefill; optional ObjectCache prefix KV
                     injection [L,2,B,P,KV,dh]; returns last logits + cache
  ``decode_step``  — one token against a [L,2,B,S,KV,dh] cache (serve_step)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .scan_util import layer_scan
from . import layers as nn


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_layer(key, cfg: ModelConfig):
    ka, km = jax.random.split(key)
    return {
        "ln1": nn.init_rmsnorm(cfg.d_model, nn.pdt(cfg)),
        "attn": nn.init_attention(ka, cfg),
        "ln2": nn.init_rmsnorm(cfg.d_model, nn.pdt(cfg)),
        "mlp": nn.init_mlp(km, cfg),
    }


def init_params(key, cfg: ModelConfig):
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    return {
        "embed": nn.init_embedding(ke, cfg),
        "layers": stacked,
        "final_norm": nn.init_rmsnorm(cfg.d_model, nn.pdt(cfg)),
    }


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------
def block(p, cfg: ModelConfig, x, positions, prefix_kv=None):
    """Pre-norm transformer block; returns (x, (k, v) of this segment)."""
    h, seg_kv = nn.attention(p["attn"], cfg, nn.rmsnorm(p["ln1"], x),
                             positions=positions, causal=True,
                             prefix_kv=prefix_kv)
    x = x + h
    x = x + nn.mlp(p["mlp"], nn.rmsnorm(p["ln2"], x), cfg.mlp_kind)
    return x, seg_kv


def decode_block(p, cfg: ModelConfig, x, k_cache, v_cache, pos):
    h, (k_cache, v_cache) = nn.decode_attention(
        p["attn"], cfg, nn.rmsnorm(p["ln1"], x), k_cache, v_cache, pos)
    x = x + h
    x = x + nn.mlp(p["mlp"], nn.rmsnorm(p["ln2"], x), cfg.mlp_kind)
    return x, k_cache, v_cache


def block_packed(p, cfg: ModelConfig, x, positions, packed_kv, *, bits: int,
                 group: int, chunk_tokens: int):
    """`block` with a quantized-resident prefix (see
    `layers.attention_packed_prefix`); returns (x, (k, v) of this suffix)."""
    h, seg_kv = nn.attention_packed_prefix(
        p["attn"], cfg, nn.rmsnorm(p["ln1"], x), packed_kv,
        positions=positions, bits=bits, group=group,
        chunk_tokens=chunk_tokens)
    x = x + h
    x = x + nn.mlp(p["mlp"], nn.rmsnorm(p["ln2"], x), cfg.mlp_kind)
    return x, seg_kv


def decode_block_packed(p, cfg: ModelConfig, x, packed_kv, sk_cache, sv_cache,
                        pos, *, bits: int, group: int, chunk_tokens: int):
    """`decode_block` against packed prefix + fp suffix cache."""
    h, (sk_cache, sv_cache) = nn.decode_attention_packed_prefix(
        p["attn"], cfg, nn.rmsnorm(p["ln1"], x), packed_kv, sk_cache,
        sv_cache, pos, bits=bits, group=group, chunk_tokens=chunk_tokens)
    x = x + h
    x = x + nn.mlp(p["mlp"], nn.rmsnorm(p["ln2"], x), cfg.mlp_kind)
    return x, sk_cache, sv_cache


# ---------------------------------------------------------------------------
# model fns
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, tokens, *, embeds: Optional[jnp.ndarray] = None,
            remat: bool = False):
    """[B,S] -> hidden [B,S,d].  ``embeds`` optionally prepends precomputed
    continuous embeddings (VLM patches, audio frames)."""
    x = nn.embed(params["embed"], cfg, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    positions = jnp.arange(x.shape[1])[None, :]

    def body(h, layer_p):
        h, _ = block(layer_p, cfg, h, positions)
        return h, None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = layer_scan(body_fn, x, params["layers"])
    return nn.rmsnorm(params["final_norm"], x)


def loss(params, cfg: ModelConfig, batch, *, remat: bool = False):
    x = forward(params, cfg, batch["tokens"],
                embeds=batch.get("embeds"), remat=remat)
    if "embeds" in batch:  # loss only over the text positions
        x = x[:, batch["embeds"].shape[1]:, :]
    lg = nn.logits(params["embed"], cfg, x)
    return nn.cross_entropy(lg, batch["labels"], batch.get("loss_mask"))


def prefill(params, cfg: ModelConfig, tokens, prefix_kv=None,
            prefix_len: int = 0, embeds=None):
    """Compute the (suffix) prompt; returns (last-token logits, kv [L,2,B,S_total,KV,dh]).

    ``prefix_kv``: ObjectCache-matched KV [L, 2, B, P, KV, dh] (or None).
    The returned cache contains prefix + suffix so decode sees the full context.
    """
    x = nn.embed(params["embed"], cfg, tokens)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = prefix_len + jnp.arange(S)[None, :]

    def body(h, xs):
        layer_p, pkv = xs
        h, seg = block(layer_p, cfg, h, positions,
                       prefix_kv=None if pkv is None else (pkv[0], pkv[1]))
        return h, jnp.stack(seg)  # [2, B, S, KV, dh]

    xs = (params["layers"], prefix_kv)
    x, seg_kv = layer_scan(body, x, xs)
    x = nn.rmsnorm(params["final_norm"], x)
    lg = nn.logits(params["embed"], cfg, x[:, -1:, :])[:, 0, :]
    if prefix_kv is not None:
        full_kv = jnp.concatenate([prefix_kv.astype(seg_kv.dtype), seg_kv], axis=3)
    else:
        full_kv = seg_kv
    return lg, full_kv


def decode_step(params, cfg: ModelConfig, cache, token, pos):
    """One decode step.  cache: [L, 2, B, S, KV, dh]; token: [B, 1]; pos: [B].

    Returns (logits [B, V], new cache).  serve_step of the dry run.
    """
    x = nn.embed(params["embed"], cfg, token)

    def body(h, xs):
        layer_p, kv = xs
        h, k_c, v_c = decode_block(layer_p, cfg, h, kv[0], kv[1], pos)
        return h, jnp.stack([k_c, v_c])

    x, new_cache = layer_scan(body, x, (params["layers"], cache))
    x = nn.rmsnorm(params["final_norm"], x)
    lg = nn.logits(params["embed"], cfg, x)[:, 0, :]
    return lg, new_cache


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return jnp.zeros((cfg.num_layers, 2, batch, seq_len, cfg.num_kv_heads,
                      cfg.head_dim), nn.dt(cfg))
