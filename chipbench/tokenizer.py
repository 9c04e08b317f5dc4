"""How a request becomes token ids: the byte tokenizer the served
requests go through, kept with the benchmark so that the reference reads
the same ids without importing the program.  4 specials + 256 bytes; the
yes/no tokens of a SCORE are the bytes 'y' and 'n'."""
from __future__ import annotations

from typing import List, Optional

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2
OFFSET = 4
YES_ID = ord("y") + OFFSET
NO_ID = ord("n") + OFFSET
CLASSIFY_SUFFIX = "\nanswer: "


def encode(text: str, *, bos: bool = True,
           max_len: Optional[int] = None) -> List[int]:
    """Byte ids; past ``max_len`` the tail is kept (and BOS, if any)."""
    ids = ([BOS_ID] if bos else []) + [b + OFFSET for b in text.encode()]
    if max_len is not None and len(ids) > max_len:
        ids = ids[:1] + ids[-(max_len - 1):] if bos else ids[-max_len:]
    return ids
