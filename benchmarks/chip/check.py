"""The comparison that decides ``correct``: what the timed path produced,
at the timed sizes, against the plain references.

Numbers compared (each with its limit from ``limits/<cell>.json``; a
number that is not there is exact and has the limit 0):

* ``prompt_mismatches``: served prompts whose token ids differ from the
  reference tokenizer's, or that exceed half of ``max_len`` and so were cut;
* ``text_mismatches``: answers whose text is not the decoding of the token
  ids the scheduler served (replay: not the response cached for the prompt);
* ``score_mismatches``: per-example lexical scores that differ from the
  reference scorers by any amount;
* ``bertscore_gap``: the widest BERTScore F1 gap to the float64 reference;
* ``ci_gap``: the widest distance of a CI end from the reference
  Poisson-bootstrap CI over the same scores, as a share of its width;
* ``logit_gap``: over a sample of finished requests drawn from the seed,
  with the longest among them, the widest gap by which a served token's
  logit lies below the reference's best at its position;
* ``engine_calls``: engine calls made by a replay (must be none);
* ``replicas_short``: where the mix samples ``per_replica`` requests of
  each replica, the replicas that finished fewer (must be none).

With ``control=True`` the same numbers are also read off the controls: the
reference itself computed one precision lower than the program computes,
put in the program's place: fp8 weights for the bfloat16 model, fp8 inputs
for the statistics kernels (whose float32 dots run as one bfloat16 pass).
The run's ``correct`` is then the :func:`verdict` on the controls' numbers
(the exact ones as the program read them), which has to come out false.
"""

from __future__ import annotations

import math
from collections import Counter

import numpy as np

from reference import scoring
from tokenizer import Tokenizer

#: examples at or above this index are set-up's warm-up, not the window's
WARM_BASE = 1 << 30


def _index(uid: str) -> tuple[str, int]:
    task, i = uid.rsplit(":", 1)
    return task, int(i)


def prompts_and_texts(probes, mix, vocab: int, max_len: int | None) -> dict:
    n_prompt = n_text = 0
    for _t0, _t1, rows, texts, _scores in probes.chunks:
        for row, text in zip(rows, texts):
            prompt = mix.prompt(row)
            got = probes.served.get(prompt)
            if got is None:
                n_prompt += 1
                n_text += 1
                continue
            ptoks, served, _reason = got
            tok = Tokenizer(vocab)
            want = tok.encode(prompt)
            if want != ptoks or (max_len and len(want) > max_len // 2):
                n_prompt += 1
            if tok.decode(served) != text:
                n_text += 1
    return {"prompt_mismatches": n_prompt, "text_mismatches": n_text}


def replay_texts(probes, mix) -> dict:
    """Texts the replay scored that differ from the responses set-up
    cached for those prompts."""
    bad = 0
    for _t0, _t1, rows, texts, _scores in probes.chunks:
        for row, text in zip(rows, texts):
            if text != mix.replay_response(row["cached"], mix.prompt(row)):
                bad += 1
    return {"text_mismatches": bad}


def scores(probes, control: bool) -> tuple[dict, dict]:
    n_bad, bert = 0, 0.0
    c_bert = 0.0
    for _t0, _t1, rows, texts, got in probes.chunks:
        for m, vals in got.items():
            for i, (row, text) in enumerate(zip(rows, texts)):
                want = scoring.score(m, text, row["reference"])
                have = float(vals[i])
                if m == "bertscore":
                    bert = max(bert, abs(have - want))
                    if control:
                        low = scoring.score(m, text, row["reference"], True)
                        c_bert = max(c_bert, abs(low - want))
                elif not (have == want or (math.isnan(have) and math.isnan(want))):
                    n_bad += 1
    numbers = {"score_mismatches": n_bad}
    controls = {}
    if any("bertscore" in c[4] for c in probes.chunks):
        numbers["bertscore_gap"] = bert
        if control:
            controls["bertscore_gap"] = c_bert
    return numbers, controls


def _ci_gap(ci: tuple[float, float], ref: tuple[float, float]) -> float:
    width = ref[1] - ref[0]
    d = max(abs(ci[0] - ref[0]), abs(ci[1] - ref[1]))
    if width <= 0:
        return 0.0 if d == 0 else math.inf
    return d / width


def _scores_by_task(probes) -> dict[str, dict[int, dict[str, float]]]:
    per_task: dict[str, dict[int, dict[str, float]]] = {}
    for _t0, _t1, rows, _texts, got in probes.chunks:
        for i, row in enumerate(rows):
            task, idx = _index(row["uid"])
            if idx >= WARM_BASE:
                continue
            per_task.setdefault(task, {})[idx] = {m: v[i] for m, v in got.items()}
    return per_task


def cis(probes, results: dict, stats: dict, control: bool) -> tuple[dict, dict]:
    """CI ends of every task's result against the reference bootstrap over
    the per-example scores the window's chunks produced, in order."""
    per_task = _scores_by_task(probes)
    gap = c_gap = 0.0
    n_boot, seed = stats["bootstrap_iterations"], stats.get("seed", 0)
    for task, res in results.items():
        rows = per_task.get(task, {})
        n = len(rows)
        if sorted(rows) != list(range(n)):
            return {"ci_gap": math.inf}, {}
        for m, mv in res.metrics.items():
            x = np.asarray([rows[i][m] for i in range(n)], np.float64)
            ref = scoring.bootstrap_ci(x, n_boot, seed)
            gap = max(gap, _ci_gap(tuple(mv.ci), ref))
            if control:
                low = scoring.bootstrap_ci(x, n_boot, seed, low_precision=True)
                c_gap = max(c_gap, _ci_gap(low, ref))
    return {"ci_gap": gap}, ({"ci_gap": c_gap} if control else {})


def half_chunk_ci_gap(probes, results: dict, stats: dict, chunk: int) -> float:
    """``ci_gap`` that a bootstrap leaving out the second half of every
    chunk (the mean taken over the rest) would read, over the scores the
    window produced: the reference CI with those positions weighing nothing,
    against the reference CI over all of them."""
    per_task = _scores_by_task(probes)
    gap = 0.0
    n_boot, seed = stats["bootstrap_iterations"], stats.get("seed", 0)
    for task, res in results.items():
        rows = per_task.get(task, {})
        n = len(rows)
        if sorted(rows) != list(range(n)):
            return math.inf
        kept =(np.arange(n) % chunk) < chunk // 2
        for m in res.metrics:
            x = np.asarray([rows[i][m] for i in range(n)], np.float64)
            ref = scoring.bootstrap_ci(x, n_boot, seed)
            half = scoring.bootstrap_ci(np.where(kept, x, np.nan), n_boot, seed)
            gap = max(gap, _ci_gap(half, ref))
    return gap


def sample_requests(probes, seed: int, k: int,
                    per_replica: int = 0) -> list[tuple[list[int], list[int]]]:
    """``k`` finished requests of the window drawn from the seed, the one
    with the most served tokens always among them; with ``per_replica``,
    the drawn order's first ``per_replica`` of each replica come first, so
    that every replica that served as many is compared on as many."""
    done = sorted(
        p for p in probes.served if p in probes.done_t and probes.served[p][1]
    )
    if not done:
        return []
    longest = max(done, key=lambda p: (len(probes.served[p][1]),
                                       len(probes.served[p][0]), p))
    rest = [p for p in done if p != longest]
    rng = np.random.default_rng([seed % (1 << 63), 7])
    order = [rest[i] for i in rng.permutation(len(rest))]
    if per_replica:
        taken = Counter([probes.replica.get(longest, 0)])
        first, later = [], []
        for p in order:
            r = probes.replica.get(p, 0)
            (first if taken[r] < per_replica else later).append(p)
            taken[r] += 1
        order = first + later
        k = max(k, 1 + len(first))
    pick = order[: max(0, k - 1)]
    return [probes.served[p][:2] for p in [longest] + pick]


def replicas_short(probes, n_replicas: int, per_replica: int) -> int:
    """Replicas that finished fewer than ``per_replica`` requests with
    served tokens, and so are compared on fewer."""
    served = Counter(
        probes.replica.get(p, 0) for p in probes.served
        if p in probes.done_t and probes.served[p][1]
    )
    return sum(served[r] < per_replica for r in range(n_replicas))


def logits(reference, requests, control: bool) -> tuple[dict, dict]:
    seqs, positions, targets = [], [], []
    for ptoks, served in requests:
        seqs.append(list(ptoks) + list(served[:-1]))
        positions.append([len(ptoks) - 1 + j for j in range(len(served))])
        targets.extend(served)
    targets = np.asarray(targets, np.int32)
    hid = reference.hidden(seqs, positions)
    best, at, _ = reference.logit_stats(hid, targets)
    numbers = {"logit_gap": float(np.max(best - at)),
               "served_tokens": int(len(targets))}
    controls = {}
    if control:
        hid_q = reference.hidden(seqs, positions, quantized=True)
        _, _, first = reference.logit_stats(hid_q, targets, quantized=True)
        best, at_q, _ = reference.logit_stats(hid, first.astype(np.int32))
        controls["logit_gap"] = float(np.max(best - at_q))
    return numbers, controls


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit."""
    shown = {}
    ok = True
    for name, value in numbers.items():
        if name == "served_tokens":
            continue
        limit = limits.get(name, 0)
        shown[name] = {"value": value, "limit": limit}
        if not value <= limit:
            ok = False
    return ok, shown
