"""The one traffic generator: reads a mix file (``traffic/<name>.json``) and
makes the cell's tasks and examples from ``--seed``.

Every seed draws the same multiset of prompt and answer lengths, in another
order, so a seed changes what the prompts say and not how much work they
are.  Lengths are counted in tokens of :mod:`tokenizer`, BOS included.

A mix of kind ``suite`` is a set of streaming tasks run together:

* ``tasks``: one entry per task, ``{"id", "header_tokens", "answer"}``.
  ``header_tokens`` counts BOS plus a seeded k-shot header that every prompt
  of the task shares (1 = no header); ``answer`` names a reference length
  in ``reference_words``.
* ``body_tokens``: the tokens after the header (template text included),
  drawn as ``n`` quantiles of a log-normal with ``median`` and ``sigma``,
  clipped to ``[min, max]``.

A mix of kind ``replay`` also lists ``n_responses`` cached responses of
``response_words`` words, written ``shard`` at a time, one commit each.
"""

from __future__ import annotations

import math
import threading
from typing import Iterator

import numpy as np

from tokenizer import n_tokens, words

#: seeded content is drawn from this fixed word list (no articles, which
#: the lexical metrics strip)
_VOCAB_RNG = np.random.default_rng(20261016)
VOCAB = sorted(
    {
        "".join(_VOCAB_RNG.choice(list("abcdefghijklmnopqrstuvwxyz"), n))
        for n in _VOCAB_RNG.integers(3, 10, 6000)
    }
    - {"the", "and"}
)[:4096]
CHOICES = ("one", "two", "three", "four")


def quantile_lengths(spec: dict) -> list[int]:
    """``spec["n"]`` lengths at evenly spaced quantiles of a clipped
    log-normal: the fixed multiset every seed draws from."""
    n, lo, hi = spec["n"], spec["min"], spec["max"]
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        # inverse normal CDF by bisection on erf: no scipy needed
        a, b = -10.0, 10.0
        for _ in range(80):
            m = (a + b) / 2
            if 0.5 * (1 + math.erf(m / math.sqrt(2))) < p:
                a = m
            else:
                b = m
        out.append(int(min(hi, max(lo, round(math.exp(mu + sigma * a))))))
    return out


def uniform_lengths(lo: int, hi: int, n: int) -> list[int]:
    return [lo + (i * (hi - lo + 1)) // n for i in range(n)]


class Mix:
    """One traffic mix under one seed."""

    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.seed = int(seed)
        self.kind = spec["kind"]
        self.template = spec["template"]
        self.chunk = spec["streaming"]["max_memory_rows"]
        self.bodies = quantile_lengths(spec["body_tokens"])
        fixed = n_tokens(self.template.format(header="", question="")) - 1
        self.template_tokens = fixed
        self.headers = [self._header(i, t) for i, t in enumerate(spec["tasks"])]
        self.stop = threading.Event()

    # -- content ---------------------------------------------------------------

    def _rng(self, *salt: int) -> np.random.Generator:
        return np.random.default_rng([self.seed % (1 << 63), *salt])

    def _words(self, rng: np.random.Generator, n: int) -> list[str]:
        return [VOCAB[i] for i in rng.integers(0, len(VOCAB), n)]

    def _header(self, task_index: int, task: dict) -> str:
        """Seeded k-shot header of exactly ``header_tokens - 1`` tokens."""
        return self._shots(self._rng(1, task_index), task["header_tokens"] - 1)

    def _shots(self, rng: np.random.Generator, n: int) -> str:
        """``n`` tokens of k-shot lines (empty for ``n <= 0``)."""
        if n <= 0:
            return ""
        lines: list[list[str]] = []
        left = n
        while left > 0:
            shot = ["Q", ":"] + self._words(rng, int(rng.integers(5, 10)))
            shot += ["A", ":"] + self._words(rng, int(rng.integers(1, 3)))
            lines.append(shot[:left])
            left -= len(lines[-1])
        text = "\n".join(" ".join(line) for line in lines) + "\n"
        assert len(words(text)) == n, (len(words(text)), n)
        return text

    def reference(self, rng: np.random.Generator, kind: str) -> str:
        if kind == "choice":
            return CHOICES[int(rng.integers(0, len(CHOICES)))]
        lo, hi = self.spec["reference_words"][kind]
        return " ".join(self._words(rng, int(rng.integers(lo, hi + 1))))

    def row(self, task_index: int, index: int, body: int,
            header: str | None = None) -> dict:
        """Example ``index`` of a task, with ``body`` tokens after its
        header (the task's own unless given); its first word is unique, so
        no two prompts coincide."""
        task = self.spec["tasks"][task_index]
        header = self.headers[task_index] if header is None else header
        rng = self._rng(2, task_index, index)
        n_words = body - self.template_tokens
        if not header:
            n_words -= 1  # BOS sits in the body when there is no header
        if n_words < 1:
            raise ValueError(f"body of {body} tokens leaves no question words")
        uid = f"u{task_index}x{index}s{self.seed % 1000003}"
        question = " ".join([uid] + self._words(rng, n_words - 1))
        return {
            "uid": f"{task['id']}:{index}",
            "header": header,
            "question": question,
            "reference": self.reference(rng, task["answer"]),
        }

    def prompt(self, row: dict) -> str:
        return self.template.format(**row)

    # -- streams ---------------------------------------------------------------

    def body_order(self, task_index: int, cycle: int) -> list[int]:
        rng = self._rng(3, task_index, cycle)
        return [self.bodies[i] for i in rng.permutation(len(self.bodies))]

    def stream(self, task_index: int, first: int = 0) -> Iterator[dict]:
        """Endless examples of one task in whole chunks; stops at the first
        chunk boundary after :attr:`stop` is set."""
        index = first
        while True:
            if index % self.chunk == 0 and self.stop.is_set():
                return
            cycle, k = divmod(index, len(self.bodies))
            yield self.row(task_index, index, self.body_order(task_index, cycle)[k])
            index += 1

    def warmup_rows(self, task_index: int, page: int) -> list[dict]:
        """Examples that reach every shape the task's traffic can give the
        paged cache: one prompt per count of body pages.  Where prompts
        share headers, task 0 also sweeps every count of pages a prompt can
        write or read (:meth:`page_sweep`).  Task 0 pads its list to whole
        chunks, so the statistics kernel meets only the window's chunk
        shape."""
        by_pages: dict[int, int] = {}
        for b in sorted(self.bodies):
            by_pages.setdefault(-(-b // page), b)
        bodies = list(by_pages.values())
        if self.spec["tasks"][task_index]["header_tokens"] > 1:
            # the first prompt of a header finds nothing to share: one more
            # so that every count of body pages is also met behind the header
            bodies = bodies[:1] + bodies
        base = 1 << 30  # far from the window's example indices
        rows = [self.row(task_index, base + i, b) for i, b in enumerate(bodies)]
        if task_index == 0:
            if any(t["header_tokens"] > 1 for t in self.spec["tasks"]):
                rows += self.page_sweep(base + len(rows), page)
            for b in self.bodies[: -len(rows) % self.chunk]:
                rows.append(self.row(task_index, base + len(rows), b))
        return rows

    def page_sweep(self, first: int, page: int) -> list[dict]:
        """Prompts of task 0 that make an admission write, and read, every
        count of pages one prompt can have.  A header that the prefix cache
        evicts in the window makes the next prompt of its task write all of
        its pages, or read only the part still cached, and each count of
        pages is a program of its own.  One prompt with a fresh prefix per
        count of prompt pages, then one per count of shared pages that
        shares that many with the longest of them."""
        top = self.spec["engine"]["max_len"] // 2 // page
        body = min(self.bodies)
        rows = []
        for n in range(1, top + 1):
            # BOS and the header's words, or BOS alone in the body
            h = n * page - body if n * page - body > 1 else 0
            header = self._shots(self._rng(7, n), h - 1)
            rows.append(self.row(0, first + len(rows), n * page - h, header))
        longest = words(rows[-1]["header"])
        for m in range(1, top):
            header = " ".join(longest[: m * page - 1]) + "\n"
            rows.append(self.row(0, first + len(rows), body, header))
        return rows

    # -- replay ----------------------------------------------------------------

    def replay_rows(self) -> list[dict]:
        spec = self.spec["replay"]
        n = spec["n_responses"]
        order = self._rng(4).permutation(n)
        bodies = [self.bodies[i % len(self.bodies)] for i in order]
        return [self.row(0, i, bodies[i]) for i in range(n)]

    def replay_response(self, index: int, prompt: str) -> str:
        """A cached response: words of the prompt and of the vocabulary, as
        greedy decoding of a model served with this tokenizer gives them."""
        lo, hi = self.spec["replay"]["response_words"]
        n_resp = self.spec["replay"]["n_responses"]
        lengths = uniform_lengths(lo, hi, n_resp)
        rng = self._rng(5, index)
        n = lengths[int(self._rng(6).permutation(n_resp)[index])]
        pool = words(prompt) + VOCAB[:256]
        return " ".join(pool[i] for i in rng.integers(0, len(pool), n))
