"""Operations and bytes that the traffic needs, from the config's shapes.

The counts are fixed by the requests, not by what the program computes:
every layer for every prompt and fed-back token, the attention each token
needs over the positions it sees, and the head only for the logits a
request reads -- the full vocabulary at each generated or label-scored
position, the yes and no rows for a SCORE.  A program that computes more
(padding, recomputed prompts, full-vocabulary rows nobody reads) does not
raise the count, so a share of the peak built on it can only be lowered by
waste.

What a token costs in a layer is the architecture's to say: ``shape`` is
the ``Shape`` of the configuration's ``reference/<model_type>.py``, and
these functions ask it through ``extend``, ``head_rows`` and
``decode_attention``, and read its ``vocab``.
"""
from __future__ import annotations

from typing import Sequence


def score(s, prompt: int) -> float:
    return s.extend(0, prompt) + s.head_rows(2)


def complete(s, prompt: int, generated: int) -> float:
    fed = prompt + max(generated - 1, 0)
    return s.extend(0, fed) + s.head_rows(generated) * s.vocab


def classify(s, prompt: int, labels: Sequence[int]) -> float:
    """The prompt once, then each label's tokens after it."""
    return (s.extend(0, prompt) + sum(s.extend(prompt, n) for n in labels)
            + s.head_rows(sum(labels)) * s.vocab)


def decode_attention(s, length: int):
    """(FLOPs, bytes) of one flash-decode call for one sequence whose cache
    holds ``length`` valid positions, over every layer."""
    return s.decode_attention(length)
