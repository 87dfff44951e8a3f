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

Where ``inference`` asks for ``n_replicas`` > 1 behind the
``prefix_affinity`` router, task ``i`` is pinned to replica ``i mod n``: its
seeded header is drawn again, with a salt of its own, until the router's
hash (:func:`route`, a copy of ``ReplicaRouter.route``) sends its prompts
there, and set-up warms every replica alike (:meth:`Mix.warmup_rows`).  With
one replica the rows are those of a mix that names none.

A mix of kind ``replay`` also lists ``n_responses`` cached responses of
``response_words`` words, written ``shard`` at a time, one commit each.
"""

from __future__ import annotations

import hashlib
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
#: draws tried before a row that has to reach one replica gives up
MAX_DRAWS = 10_000
#: index step between two draws of one warm-up row, past any row's index
DRAW_STRIDE = 1 << 12


def route(prompt: str, n_replicas: int, prefix_len: int) -> int:
    """The replica that the program's ``prefix_affinity`` router sends
    ``prompt`` to among ``n_replicas`` live ones: the sha256 of its first
    ``prefix_len`` characters, the digest's first 8 bytes read big-endian,
    modulo the count (``repro.core.service.ReplicaRouter.route``)."""
    if n_replicas <= 1:
        return 0
    h = hashlib.sha256(prompt[:prefix_len].encode("utf-8", "replace")).digest()
    return int.from_bytes(h[:8], "big") % n_replicas


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
        inf = spec.get("inference", {})
        self.n_replicas = inf.get("n_replicas", 1)
        self.prefix_len = 0
        if self.n_replicas > 1:
            if inf.get("routing") != "prefix_affinity":
                raise ValueError("tasks are pinned to replicas by prefix_affinity only")
            self.prefix_len = inf["routing_prefix_len"]
        self.headers = [self._header(i, t) for i, t in enumerate(spec["tasks"])]
        self.stop = threading.Event()

    # -- content ---------------------------------------------------------------

    def _rng(self, *salt: int) -> np.random.Generator:
        return np.random.default_rng([self.seed % (1 << 63), *salt])

    def _words(self, rng: np.random.Generator, n: int) -> list[str]:
        return [VOCAB[i] for i in rng.integers(0, len(VOCAB), n)]

    def replica_of(self, prompt: str) -> int:
        return route(prompt, self.n_replicas, self.prefix_len)

    def _header(self, task_index: int, task: dict) -> str:
        """Seeded k-shot header of exactly ``header_tokens - 1`` tokens;
        over replicas, the first draw whose prompts route to the task's
        replica."""
        n = task["header_tokens"] - 1
        if self.n_replicas == 1:
            return self._shots(self._rng(1, task_index), n)
        want = task_index % self.n_replicas
        for attempt in range(MAX_DRAWS):
            header = self._shots(self._rng(8, task_index, attempt), n)
            lead = self.template.split("{question}")[0].format(header=header)
            if len(lead) < self.prefix_len:
                raise ValueError(
                    f"task {task['id']}: a header of {n} tokens does not cover "
                    f"the router's {self.prefix_len} characters"
                )
            if self.replica_of(lead) == want:
                return header
        raise RuntimeError(f"task {task['id']}: no header routes to replica {want}")

    def _pinned(self, make, replica: int, also=None) -> dict:
        """The first of ``make(0)``, ``make(1)``, ... whose prompt, and the
        text ``also(row)`` where given, route to ``replica`` (``make(0)``
        alone where there is one replica)."""
        for attempt in range(MAX_DRAWS):
            row = make(attempt)
            texts = [self.prompt(row)] + ([also(row)] if also else [])
            if all(self.replica_of(t) == replica for t in texts):
                return row
        raise RuntimeError(f"no warm-up row routes to replica {replica}")

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
        share headers, the first task of each replica also sweeps every
        count of pages a prompt can write or read there
        (:meth:`page_sweep`).  Task 0 pads its list to whole chunks, so the
        statistics kernel meets only the window's chunk shape."""
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
        if task_index < self.n_replicas:
            if any(t["header_tokens"] > 1 for t in self.spec["tasks"]):
                rows += self.page_sweep(task_index, base + len(rows), page)
        if task_index == 0:
            for b in self.bodies[: -len(rows) % self.chunk]:
                rows.append(self.row(task_index, base + len(rows), b))
        return rows

    def page_sweep(self, task_index: int, first: int, page: int) -> list[dict]:
        """Prompts of a task that make an admission write, and read, every
        count of pages one prompt can have, on the replica the task is
        pinned to.  A header that the prefix cache evicts in the window
        makes the next prompt of its task write all of its pages, or read
        only the part still cached, and each count of pages is a program of
        its own on each replica.  One prompt with a fresh prefix per count
        of prompt pages, then one per count of shared pages that shares
        that many with the longest of them.  Over replicas, a row whose
        draw routes elsewhere is drawn again (header and question), and the
        longest until the shared headers cut from it route there too."""
        replica = task_index % self.n_replicas
        top = self.spec["engine"]["max_len"] // 2 // page
        body = min(self.bodies)
        rows: list[dict] = []

        def index(attempt: int) -> int:
            return first + len(rows) + attempt * DRAW_STRIDE

        for n in range(1, top + 1):
            # BOS and the header's words, or BOS alone in the body
            h = n * page - body if n * page - body > 1 else 0

            def fresh(attempt, n=n, h=h):
                salt = (7, n) if attempt == 0 else (7, n, replica, attempt)
                header = self._shots(self._rng(*salt), h - 1)
                return self.row(task_index, index(attempt), n * page - h, header)

            # the shared headers below join the longest's words on one line
            shared = (lambda row: " ".join(words(row["header"]))) if n == top else None
            rows.append(self._pinned(fresh, replica, shared))
        longest = words(rows[-1]["header"])
        for m in range(1, top):
            header = " ".join(longest[: m * page - 1]) + "\n"
            rows.append(self._pinned(
                lambda attempt, header=header: self.row(
                    task_index, index(attempt), body, header
                ),
                replica,
            ))
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
