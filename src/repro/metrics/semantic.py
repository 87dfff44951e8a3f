"""Semantic metrics (paper §4.1): embedding cosine similarity + BERTScore.

Offline substitute for sentence-transformers / roberta-large: a
deterministic **feature-hashing embedder** (char-n-gram + word hashing into
a fixed-dimension space, L2-normalized).  It preserves the property the
metrics need — similar surface forms map to nearby vectors — and is
identical across processes/hosts.  On a real deployment the embedder is
swappable for model-based encoders (the LocalJaxEngine exposes hidden
states; see ``model_embedder``).

BERTScore greedy matching runs through ``repro/kernels/bertscore``: with
``use_pallas`` the Pallas kernel (native on a TPU, interpreted elsewhere),
otherwise the jnp oracle.
"""

from __future__ import annotations

import hashlib

import numpy as np

from repro.kernels.bertscore.ref import bertscore_ref


class HashEmbedder:
    """Deterministic n-gram feature-hashing embedder."""

    def __init__(self, dim: int = 256, ngram: tuple[int, int] = (3, 5)):
        self.dim = dim
        self.ngram = ngram

    def _features(self, text: str) -> list[str]:
        text = " ".join(text.lower().split())
        feats = text.split()
        padded = f" {text} "
        lo, hi = self.ngram
        for n in range(lo, hi + 1):
            feats.extend(padded[i : i + n] for i in range(len(padded) - n + 1))
        return feats

    def _bucket(self, feat: str) -> tuple[int, float]:
        h = hashlib.md5(feat.encode()).digest()
        idx = int.from_bytes(h[:4], "little") % self.dim
        sign = 1.0 if h[4] & 1 else -1.0
        return idx, sign

    def embed(self, text: str) -> np.ndarray:
        v = np.zeros(self.dim, np.float32)
        for f in self._features(text):
            idx, sign = self._bucket(f)
            v[idx] += sign
        n = np.linalg.norm(v)
        return v / n if n > 0 else v

    def embed_batch(self, texts: list[str]) -> np.ndarray:
        return np.stack([self.embed(t) for t in texts])

    def embed_tokens(self, text: str, max_len: int) -> tuple[np.ndarray, np.ndarray]:
        """Per-word embeddings (for BERTScore): (max_len, dim), mask."""
        words = text.lower().split()[:max_len]
        out = np.zeros((max_len, self.dim), np.float32)
        mask = np.zeros(max_len, np.float32)
        for i, w in enumerate(words):
            out[i] = self.embed(w)
            mask[i] = 1.0
        return out, mask


_DEFAULT = HashEmbedder()


def embedding_similarity(
    preds: list[str], refs: list[str], embedder: HashEmbedder | None = None
) -> np.ndarray:
    emb = embedder or _DEFAULT
    p = emb.embed_batch(preds)
    r = emb.embed_batch(refs)
    return np.clip(np.sum(p * r, axis=1), -1.0, 1.0).astype(np.float64)


def bertscore_f1(
    preds: list[str],
    refs: list[str],
    embedder: HashEmbedder | None = None,
    *,
    max_len: int = 64,
    use_pallas: bool = False,
) -> np.ndarray:
    emb = embedder or _DEFAULT
    cand = np.zeros((len(preds), max_len, emb.dim), np.float32)
    ref = np.zeros((len(refs), max_len, emb.dim), np.float32)
    cmask = np.zeros((len(preds), max_len), np.float32)
    rmask = np.zeros((len(refs), max_len), np.float32)
    for i, (p, r) in enumerate(zip(preds, refs)):
        cand[i], cmask[i] = emb.embed_tokens(p, max_len)
        ref[i], rmask[i] = emb.embed_tokens(r, max_len)
    if use_pallas:
        import jax

        from repro.kernels.bertscore import bertscore

        # native kernel on a TPU; the Pallas interpreter everywhere else
        _, _, f1 = bertscore(
            cand, ref, cmask, rmask, use_pallas=True,
            interpret=jax.default_backend() != "tpu",
        )
    else:
        _, _, f1 = bertscore_ref(cand, ref, cmask, rmask)
    return np.asarray(f1, np.float64)
