"""The plain reference that decides ``correct``, and its lower-precision
control.

A Qwen3-style decoder written from the configuration file alone: RMSNorm
(eps from the file), per-head q/k RMSNorm, rotary embeddings on the two
halves of each head, grouped-query causal attention, SwiGLU, and a tied or
separate output table.  It imports nothing of the program; it reads the
weights that ``weights.make_params`` made, by their names in the tree.

It runs in float32 with ``highest`` matmul precision, one layer at a time,
one sequence at a time.  A document's keys and values are computed once and
reused for every request on that document: the same arithmetic as one pass
over the whole prompt, at a fraction of the cost.

The configuration states how a document is committed (``serving``:
``commit_section_tokens``) and how its keys and values cross the wire
(``bits``, ``group``, ``chunk_tokens``), and the reference applies both
from their definitions.  Each section attends to the rounded sections
before it, as a warm hit does; the rounding is per chunk of
``chunk_tokens`` tokens and per ``group`` consecutive channels of the
flattened heads, one scale = absmax / (2^(bits-1) - 1) rounded to float16,
values rounded half to even and clipped to +-(2^(bits-1) - 1).  The keys
are rounded after the rotary embedding.  Suffix and decoded tokens keep
full precision, as a served request keeps them.

``quant="fp8"`` is the control: every matmul operand (weights, activations,
queries, keys, values, attention probabilities) is rounded to float8 e4m3
with a per-tensor scale before the product, the next precision below the
bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384


def _fq(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def codec_round(x, bits: int, group: int, chunk: int):
    """x [P, KV, dh] -> x quantized and dequantized as the stated codec
    does (see the module docstring)."""
    P = x.shape[0]
    qmax = float((1 << (bits - 1)) - 1)
    xg = x.reshape(P // chunk, chunk, -1, group)
    absmax = jnp.max(jnp.abs(xg), axis=(1, 3), keepdims=True)
    f16max = float(jnp.finfo(jnp.float16).max)
    s = jnp.minimum(absmax / qmax, f16max).astype(jnp.float16).astype(
        jnp.float32)
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(xg / s), -qmax, qmax)
    return (q * s).reshape(x.shape)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


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
    P = pk.shape[0]
    qg = q.reshape(S, KV, H // KV, dh)
    s = jnp.einsum("sgrd,tgd->grst", _fq(qg, quant), _fq(K, quant))
    s = s / math.sqrt(dh)
    visible = jnp.arange(P + S)[None, :] <= (P + jnp.arange(S))[:, None]
    s = jnp.where(visible, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grst,tgd->sgrd", _fq(p, quant), _fq(V, quant))
    x = x + mm(o.reshape(S, H * dh), at["wo"]["w"])
    h = _rms(x, lp["ln2"]["scale"], eps)
    g = jax.nn.silu(mm(h, ml["wi_gate"]["w"])) * mm(h, ml["wi_up"]["w"])
    return x + mm(g, ml["wo"]["w"]), k, v


@functools.partial(jax.jit, static_argnames=("size", "eps", "quant"))
def _head_block(x, final_scale, table, start, *, size, eps, quant):
    """Logits of rows x [n, d] over vocabulary rows [start, start+size)."""
    blk = jax.lax.dynamic_slice_in_dim(table, start, size, 0)
    h = _rms(x, final_scale, eps)
    return jnp.matmul(_fq(h, quant), _fq(blk.astype(jnp.float32), quant).T)


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
        follow a ``P``-token prefix whose per-layer kv is ``prefix``.  The
        rows are padded to a power of two (at least 64) so that few shapes
        compile; causality keeps the padding out of every real row."""
        S = len(tokens)
        Sp = max(64, 1 << (S - 1).bit_length())
        padded = np.zeros(Sp, np.int32)
        padded[:S] = tokens
        x, _ = self._layers(padded, P + np.arange(Sp), prefix)
        rows = x[first:first + n]
        size = min(VOCAB_BLOCK, self.V)
        starts = list(range(0, self.V - size, size)) + [self.V - size]
        pieces, end = [], 0
        with jax.default_matmul_precision("highest"):
            for s in starts:
                lg = _head_block(rows, self.params["final_norm"]["scale"],
                                 self.out_table, s, size=size,
                                 eps=self.dims[3], quant=self.quant)
                pieces.append(lg[:, end - s:])
                end = s + size
        return jnp.concatenate(pieces, axis=1)


def widest_gap(ref_logits: jnp.ndarray, tokens) -> float:
    """max over rows of (the reference's best logit - its logit of the
    token that was served there)."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tok[:, None], axis=-1)[:, 0]
    return float(jnp.max(best - got))
