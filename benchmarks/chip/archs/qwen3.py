"""Qwen3 (``model_type`` ``"qwen3"``): the program's configuration for a
file, the plain reference, and the operations a prefill or a decoded token
needs.

The reference is a Qwen3 decoder written from the configuration file
alone: RMSNorm (eps from the file), per-head q/k RMSNorm, rotary
embeddings on the two halves of each head, grouped-query causal attention,
SwiGLU, and a tied or separate output table.  It imports nothing of the
program; it reads the weights that ``weights.make_params`` made, by their
names in the tree.  It runs in float32 with ``highest`` matmul precision,
one layer at a time, one sequence at a time.  A document's keys and values
are computed once and reused for every request on that document: the same
arithmetic as one pass over the whole prompt, at a fraction of the cost.

The configuration states how a document is committed (``serving``:
``commit_section_tokens``) and how its keys and values cross the wire
(``bits``, ``group``, ``chunk_tokens``), and the reference applies both
(``chipbench.reference.codec_round``).  Each section attends to the
rounded sections before it, as a warm hit does; K and V are rounded as
``[P, KV, dh]``, the keys after the rotary embedding.  Suffix and decoded
tokens keep full precision, as a served request keeps them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import (_fq, _rms, causal_attention, codec_round,
                                 head, pad_rows)

# configuration-file key -> the program's ModelConfig field
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


def program_config(conf: dict):
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


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs        # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]     # [S, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(lp, x, pk, pv, positions, *, dims, quant):
    """x [S, d] f32; pk/pv [P, KV, dh] f32 (P may be 0).  Returns x and this
    segment's post-rotary k, v."""
    H, KV, dh, eps, theta = dims
    S = x.shape[0]

    def mm(a, w):
        return jnp.matmul(_fq(a, quant), _fq(w.astype(jnp.float32), quant))

    at, ml = lp["attn"], lp["mlp"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    q = mm(h, at["wq"]["w"]).reshape(S, H, dh)
    k = mm(h, at["wk"]["w"]).reshape(S, KV, dh)
    v = mm(h, at["wv"]["w"]).reshape(S, KV, dh)
    q = _rope(_rms(q, at["q_norm"]["scale"], eps), positions, theta)
    k = _rope(_rms(k, at["k_norm"]["scale"], eps), positions, theta)
    K = jnp.concatenate([pk, k], 0)
    V = jnp.concatenate([pv, v], 0)
    o = causal_attention(q.reshape(S, KV, H // KV, dh), K, V, quant)
    x = x + mm(o.reshape(S, H * dh), at["wo"]["w"])
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = jax.nn.silu(mm(h, ml["wi_gate"]["w"])) * mm(h, ml["wi_up"]["w"])
    return x + mm(g, ml["wo"]["w"]), k, v


class Reference:
    def __init__(self, params, conf: dict, quant: Optional[str] = None):
        self.params = params
        self.quant = quant
        self.L = int(conf["num_hidden_layers"])
        self.V = int(conf["vocab_size"])
        self.dims = (int(conf["num_attention_heads"]),
                     int(conf["num_key_value_heads"]), int(conf["head_dim"]),
                     float(conf["rms_norm_eps"]), float(conf["rope_theta"]))
        emb = params["embed"]
        self.table = emb["table"]
        if conf["tie_word_embeddings"] == ("unembed" in emb):
            raise ValueError("the parameter tree does not match "
                             "tie_word_embeddings")
        self.out_table = emb.get("unembed", emb["table"])
        dh = self.dims[2]
        self._empty = jnp.zeros((0, self.dims[1], dh), jnp.float32)
        sv = conf["serving"]
        self.codec = (int(sv["bits"]), int(sv["group"]),
                      int(sv["chunk_tokens"]))
        self.section = int(sv["commit_section_tokens"])

    def _layers(self, tokens, positions, prefix):
        x = jnp.take(self.table, jnp.asarray(tokens), axis=0).astype(
            jnp.float32)
        pos = jnp.asarray(positions, jnp.int32)
        kv = []
        with jax.default_matmul_precision("highest"):
            for l in range(self.L):
                lp = jax.tree.map(lambda a: a[l], self.params["layers"])
                pk, pv = prefix[l] if prefix is not None else (self._empty,
                                                               self._empty)
                x, k, v = _layer(lp, x, pk, pv, pos, dims=self.dims,
                                 quant=self.quant)
                kv.append((k, v))
        return x, kv

    def prefix_kv(self, tokens: np.ndarray):
        """Per-layer (k, v) [P, KV, dh] of a document at positions 0..P-1
        as it was committed: section by section (``commit_section_tokens``),
        each section attending to the rounded sections before it, and each
        rounded as the stated codec rounds a committed chunk."""
        rnd = jax.jit(lambda a: codec_round(a, *self.codec))
        kv = None
        for lo in range(0, len(tokens), self.section):
            hi = lo + self.section
            _, seg = self._layers(tokens[lo:hi], np.arange(lo, hi), kv)
            seg = [(rnd(k), rnd(v)) for k, v in seg]
            kv = seg if kv is None else [
                (jnp.concatenate([pk, k]), jnp.concatenate([pv, v]))
                for (pk, pv), (k, v) in zip(kv, seg)]
        return kv

    def logits(self, prefix, P: int, tokens: np.ndarray, first: int,
               n: int) -> jnp.ndarray:
        """Logits [n, V] at rows ``first .. first+n-1`` of ``tokens``, which
        follow a ``P``-token prefix whose per-layer kv is ``prefix``."""
        padded = pad_rows(tokens)
        x, _ = self._layers(padded, P + np.arange(len(padded)), prefix)
        return head(x[first:first + n], self.params["final_norm"]["scale"],
                    self.out_table, self.V, self.dims[3], self.quant)
