"""Operations and bytes that the traffic needs, from the config's shapes.

The counts are fixed by the requests, not by what the program computes:
every layer for every prompt and fed-back token, causal attention over the
positions before each token, and the head only for the logits a request
reads -- the full vocabulary at each generated or label-scored position,
the yes and no rows for a SCORE.  A program that computes more (padding,
recomputed prompts, full-vocabulary rows nobody reads) does not raise the
count, so a share of the peak built on it can only be lowered by waste.
"""
from __future__ import annotations

from typing import Sequence

BF16 = 2


def dense_per_token(s) -> float:
    """Projection and MLP FLOPs of one token through one layer."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return 2.0 * s.d * (q + 2 * kv) + 2.0 * q * s.d + 6.0 * s.d * s.d_ff


def attention(s, start: int, n: int) -> float:
    """Score and value FLOPs of tokens at positions start..start+n-1 of one
    layer, each attending to every position up to its own."""
    keys = n * start + n * (n + 1) / 2.0
    return 4.0 * s.heads * s.head_dim * keys


def extend(s, start: int, n: int) -> float:
    """FLOPs of n tokens after ``start`` cached ones, through every layer."""
    return s.layers * (n * dense_per_token(s) + attention(s, start, n))


def head_rows(s, rows: int) -> float:
    return 2.0 * s.d * rows


def score(s, prompt: int) -> float:
    return extend(s, 0, prompt) + head_rows(s, 2)


def complete(s, prompt: int, generated: int) -> float:
    fed = prompt + max(generated - 1, 0)
    return extend(s, 0, fed) + head_rows(s, generated) * s.vocab


def classify(s, prompt: int, labels: Sequence[int]) -> float:
    """The prompt once, then each label's tokens after it."""
    return (extend(s, 0, prompt) + sum(extend(s, prompt, n) for n in labels)
            + head_rows(s, sum(labels)) * s.vocab)


def decode_attention(s, length: int):
    """(FLOPs, bytes) of one flash-decode call for one sequence whose cache
    holds ``length`` valid positions, over every layer: q.k and p.v over
    the valid keys, and the bfloat16 K and V those keys need."""
    flops = 4.0 * s.heads * s.head_dim * length * s.layers
    nbytes = 2.0 * length * s.kv_heads * s.head_dim * BF16 * s.layers
    return flops, nbytes
