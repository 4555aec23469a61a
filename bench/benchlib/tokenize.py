"""The benchmark's own tokenizer and query pruning.

Same contract as the program's ``HashTokenizer`` and ``prune_text``
(a word maps to ``2 + md5(word) % (vocab - 2)``, 0 = pad, 1 = bos;
long queries keep their first and last words and a seeded sample of
the middle), written out again so that the plain reference never
takes token ids from the program.
"""
from __future__ import annotations

import hashlib
import re
from typing import List, Sequence

import numpy as np

PAD_ID = 0
BOS_ID = 1
_WORD_RE = re.compile(r"[a-z0-9']+")


class Tokenizer:
    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size
        self._ids: dict = {}

    def word_id(self, word: str) -> int:
        wid = self._ids.get(word)
        if wid is None:
            h = hashlib.md5(word.encode()).digest()
            wid = 2 + int.from_bytes(h[:8], "little") % (self.vocab_size - 2)
            self._ids[word] = wid
        return wid

    def encode(self, text: str, max_len: int) -> List[int]:
        ids = [BOS_ID] + [self.word_id(w)
                          for w in _WORD_RE.findall(text.lower())]
        return ids[:max_len]

    def encode_batch(self, texts: Sequence[str], max_len: int) -> np.ndarray:
        out = np.full((len(texts), max_len), PAD_ID, np.int32)
        for i, t in enumerate(texts):
            ids = self.encode(t, max_len)
            out[i, :len(ids)] = ids
        return out


def prune_text(text: str, head: int, tail: int, mid: int,
               seed: int = 0) -> str:
    """First ``head`` and last ``tail`` words plus ``mid`` words of the
    middle drawn with ``default_rng(seed + n_words)``."""
    words = text.split()
    if len(words) <= head + tail + mid:
        return text
    middle = words[head:-tail]
    rng = np.random.default_rng(seed + len(words))
    pick = sorted(rng.choice(len(middle), size=mid, replace=False))
    return " ".join(words[:head] + [middle[i] for i in pick] + words[-tail:])
