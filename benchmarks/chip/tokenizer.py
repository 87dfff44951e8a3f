"""Word-level hash tokenizer, kept with the benchmark so that the traffic
generator can size prompts in tokens and the reference can decode served
token ids without importing the program.

The served system tokenizes the same way: each word or punctuation mark is
one id, ``4 + md5(word)[:8] (little endian) mod (vocab - 4)``, after a BOS id
of 2; id 1 is EOS and ids below 4 decode to nothing.  Generated ids whose
word was never encoded decode to ``<id>``.
"""

from __future__ import annotations

import hashlib
import re

WORD_RE = re.compile(r"\w+|[^\w\s]")
EOS_ID, BOS_ID, N_SPECIAL = 1, 2, 4


def words(text: str) -> list[str]:
    return WORD_RE.findall(text)


def n_tokens(text: str) -> int:
    """Prompt length in tokens, BOS included."""
    return 1 + len(words(text))


class Tokenizer:
    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size
        self.inverse: dict[int, str] = {}

    def token_id(self, word: str) -> int:
        h = int.from_bytes(hashlib.md5(word.encode()).digest()[:8], "little")
        tid = N_SPECIAL + h % (self.vocab_size - N_SPECIAL)
        self.inverse.setdefault(tid, word)
        return tid

    def encode(self, text: str) -> list[int]:
        return [BOS_ID] + [self.token_id(w) for w in words(text)]

    def decode(self, ids: list[int]) -> str:
        out = []
        for t in ids:
            if t == EOS_ID:
                break
            if t < N_SPECIAL:
                continue
            out.append(self.inverse.get(int(t), f"<{int(t)}>"))
        return " ".join(out)
