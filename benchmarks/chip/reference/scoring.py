"""Plain references for what the statistics plane computes: per-example
lexical scores (SQuAD normalization, token F1, ROUGE-L), BERTScore over
the hash embedder in float64, and the Poisson-bootstrap percentile CI with
the counter-mixer weight stream.  Nothing here imports the program.

``low_precision`` is the control: the same computation with its inputs
rounded to fp8 (e4m3).  The statistics kernels run their float32 dots at
the default precision, one bfloat16 pass on the TPU's matrix unit, so
fp8 is the step below what they compute.
"""

from __future__ import annotations

import hashlib
import re
import string
from collections import Counter

import numpy as np

_PUNCT = str.maketrans("", "", string.punctuation)
_ARTICLES = re.compile(r"\b(a|an|the)\b")
_WS = re.compile(r"\s+")


def normalize(text: str) -> str:
    text = text.lower().translate(_PUNCT)
    return _WS.sub(" ", _ARTICLES.sub(" ", text)).strip()


def exact_match(pred: str, ref: str) -> float:
    return float(normalize(pred) == normalize(ref))


def token_f1(pred: str, ref: str) -> float:
    p, r = normalize(pred).split(), normalize(ref).split()
    if not p or not r:
        return float(p == r)
    common = sum((Counter(p) & Counter(r)).values())
    if common == 0:
        return 0.0
    prec, rec = common / len(p), common / len(r)
    return 2 * prec * rec / (prec + rec)


def rouge_l(pred: str, ref: str) -> float:
    p, r = normalize(pred).split(), normalize(ref).split()
    if not p or not r:
        return 0.0
    prev = [0] * (len(r) + 1)
    for x in p:
        cur = [0]
        for j, y in enumerate(r, 1):
            cur.append(prev[j - 1] + 1 if x == y else max(prev[j], cur[-1]))
        prev = cur
    lcs = prev[-1]
    if lcs == 0:
        return 0.0
    prec, rec = lcs / len(p), lcs / len(r)
    return 2 * prec * rec / (prec + rec)


LEXICAL = {"exact_match": exact_match, "token_f1": token_f1, "rouge_l": rouge_l}

# -- BERTScore over the hash embedder -------------------------------------------

EMBED_DIM, NGRAM, MAX_WORDS = 256, (3, 5), 64


def _features(word: str) -> list[str]:
    text = " ".join(word.lower().split())
    feats = text.split()
    padded = f" {text} "
    for n in range(NGRAM[0], NGRAM[1] + 1):
        feats.extend(padded[i : i + n] for i in range(len(padded) - n + 1))
    return feats


def embed_word(word: str) -> np.ndarray:
    v = np.zeros(EMBED_DIM, np.float64)
    for f in _features(word):
        h = hashlib.md5(f.encode()).digest()
        v[int.from_bytes(h[:4], "little") % EMBED_DIM] += 1.0 if h[4] & 1 else -1.0
    n = np.linalg.norm(v)
    return v / n if n > 0 else v


def bertscore_f1(pred: str, ref: str, low_precision: bool = False) -> float:
    """Greedy max-cosine matching of word embeddings (first 64 words)."""
    c = [embed_word(w) for w in pred.lower().split()[:MAX_WORDS]]
    r = [embed_word(w) for w in ref.lower().split()[:MAX_WORDS]]
    if not c or not r:
        # an empty side matches nothing: -1e30 maxima, as the kernel masks
        p = 0.0 if not c else -1e30
        rec = 0.0 if not r else -1e30
    else:
        cm, rm = np.stack(c), np.stack(r)
        cm = cm / np.maximum(np.linalg.norm(cm, axis=1, keepdims=True), 1e-9)
        rm = rm / np.maximum(np.linalg.norm(rm, axis=1, keepdims=True), 1e-9)
        if low_precision:
            # the normalized vectors enter the similarity matmul in fp8,
            # one scale per side, as a per-tensor fp8 kernel takes them
            cm, rm = _fp8(cm, axis=None, scaled=True), _fp8(rm, axis=None, scaled=True)
        sim = cm @ rm.T
        p, rec = float(sim.max(axis=1).mean()), float(sim.max(axis=0).mean())
    return 2 * p * rec / max(p + rec, 1e-9)


def _fp8(x: np.ndarray, axis: int | None = None, scaled: bool = False) -> np.ndarray:
    """Round to fp8 (e4m3), scaled by the absmax along ``axis`` (all of
    ``x`` when ``axis`` is None) where ``scaled``, else as they are (values
    within +-448)."""
    import ml_dtypes

    x = np.asarray(x, np.float64)
    s = 1.0
    if scaled:
        s = np.max(np.abs(x), axis=axis, keepdims=axis is not None) / 448.0
        s = np.where(s > 0, s, 1.0)
    q = (x / s).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return q.astype(np.float64) * s


def score(name: str, pred: str, ref: str, low_precision: bool = False) -> float:
    if name == "bertscore":
        return bertscore_f1(pred, ref, low_precision)
    return LEXICAL[name](pred, ref)


# -- Poisson bootstrap ------------------------------------------------------------

_POISSON1_CDF = np.asarray(
    [0.36787944117144233, 0.7357588823428847, 0.9196986029286058,
     0.9810118431238462, 0.9963401531726563, 0.9994058151824183,
     0.9999167588507119],
    np.float32,
)


def poisson_weights(n_boot: int, n: int, seed: int) -> np.ndarray:
    """(n_boot, n) Poisson(1) weights of replicate b for example i: a
    murmur3 finalizer over ``b*0x9E3779B1 ^ i*0x85EBCA77 ^ seed``, its top
    24 bits as a uniform, through the inverse CDF capped at 7."""
    u32 = np.uint32
    b = np.arange(n_boot, dtype=u32)[:, None]
    i = np.arange(n, dtype=u32)[None, :]
    with np.errstate(over="ignore"):
        h = (b * u32(0x9E3779B1)) ^ (i * u32(0x85EBCA77)) ^ u32(seed)
        h ^= h >> u32(16)
        h = h * u32(0x85EBCA6B)
        h ^= h >> u32(13)
        h = h * u32(0xC2B2AE35)
        h ^= h >> u32(16)
    u = (h >> u32(8)).astype(np.float32) * np.float32(1.0 / (1 << 24))
    return (u[..., None] >= _POISSON1_CDF).sum(axis=-1).astype(np.float64)


def bootstrap_ci(scores: np.ndarray, n_boot: int, seed: int,
                 confidence: float = 0.95, low_precision: bool = False):
    """Percentile CI of Poisson-bootstrap means over examples in position
    order (NaN scores weigh nothing)."""
    x = np.asarray(scores, np.float64)
    valid = ~np.isnan(x)
    x = np.where(valid, x, 0.0)
    if low_precision:
        x = _fp8(x)
    w = poisson_weights(n_boot, len(x), seed) * valid[None, :]
    means = (w @ x) / np.maximum(w.sum(axis=1), 1.0)
    alpha = (1 - confidence) / 2
    lo, hi = np.quantile(means, [alpha, 1 - alpha])
    return float(lo), float(hi)
