"""One generator for every traffic mix: the mix is a JSON file of parameters.

A mix is cut into blocks of ``block`` requests.  Every block holds the same
multiset of sizes and gaps — exact counts for weighted buckets, evenly
spaced quantiles of the exponential for gaps — in an order that depends
on the block's index only.  The run seed chooses the token ids (and the
documents').  So every seed offers the same work in the same arrangement:
the order of sizes and gaps sets queueing in an open loop and the tail of
a closed batch, and would otherwise make seeds differ in load.

Keys of a mix file:

* ``loop``: ``"open"`` (requests due on a schedule, ``rate_per_s``) or
  ``"closed"`` (back-to-back calls of ``batch`` requests each).
* ``documents``: ``{"count", "tokens"}`` — prompts committed in set-up;
  every request is one document followed by a fresh suffix.
* ``suffix_tokens``, ``output_tokens``: ``{"values", "weights"}`` buckets
  (``weights`` defaults to equal): an empirical distribution, or one value.
* ``block``: requests per block; each weight times ``block`` must be whole.
* ``engine``: optional serving sizes the mix needs (decode slots, max_seq).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

ORDER_SEED = 0  # seeds the arrangement of sizes and gaps in each block


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    doc: int
    suffix: np.ndarray     # fresh token ids after the document
    max_new_tokens: int
    due_s: float           # offset from the window's start (open loop)


def _bucket_counts(spec: dict, block: int) -> list[int]:
    values, weights = spec["values"], spec.get("weights",
                                               [1] * len(spec["values"]))
    total = sum(weights)
    counts = [w * block / total for w in weights]
    if any(abs(c - round(c)) > 1e-9 for c in counts):
        raise ValueError(f"block {block} does not split {weights} into "
                         f"whole counts")
    out = []
    for v, c in zip(values, counts):
        out += [int(v)] * int(round(c))
    return out


def exponential_gaps(rate_per_s: float, block: int) -> np.ndarray:
    """``block`` evenly spaced quantiles of Exp(rate), scaled so their mean
    is exactly 1/rate."""
    q = (np.arange(block) + 0.5) / block
    gaps = -np.log1p(-q)
    return gaps * (block / rate_per_s) / gaps.sum()


class Traffic:
    def __init__(self, mix: dict, vocab_size: int, seed: int) -> None:
        self.mix = mix
        self.vocab = int(vocab_size)
        self.seed = int(seed)
        self.block = int(mix["block"])
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be 'open' or 'closed', got "
                             f"{self.loop!r}")
        docs = mix["documents"]
        if self.block % docs["count"]:
            raise ValueError("block must be a multiple of the document count")
        self.suffix_sizes = _bucket_counts(mix["suffix_tokens"], self.block)
        self.output_sizes = _bucket_counts(mix["output_tokens"], self.block)
        self.gaps = (exponential_gaps(float(mix["rate_per_s"]), self.block)
                     if self.loop == "open" else np.zeros(self.block))
        rng = np.random.default_rng([self.seed, 0])
        self.documents = [rng.integers(0, self.vocab, docs["tokens"],
                                       dtype=np.int32)
                          for _ in range(docs["count"])]

    @property
    def engine(self) -> dict:
        return self.mix.get("engine", {})

    def bucket_sizes(self) -> list[int]:
        """Every suffix length the mix can send (the shapes set-up warms)."""
        return sorted(set(self.suffix_sizes))

    def requests(self) -> Iterator[Request]:
        """The endless schedule, block by block."""
        n_docs = len(self.documents)
        t, index, b = 0.0, 0, 0
        while True:
            order = np.random.default_rng([ORDER_SEED, b])
            suffix = order.permutation(self.suffix_sizes)
            out = order.permutation(self.output_sizes)
            gaps = order.permutation(self.gaps)
            docs = order.permutation(np.arange(self.block) % n_docs)
            rng = np.random.default_rng([self.seed, 1, b])
            for i in range(self.block):
                t += float(gaps[i])
                yield Request(index, int(docs[i]),
                              rng.integers(0, self.vocab, int(suffix[i]),
                                           dtype=np.int32),
                              int(out[i]), t)
                index += 1
            b += 1

    def prompt(self, req: Request) -> np.ndarray:
        return np.concatenate([self.documents[req.doc], req.suffix])
