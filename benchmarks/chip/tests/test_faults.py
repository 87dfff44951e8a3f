"""Each fault the cells can have, planted under a whole run, turns
``correct`` false; the same run without a fault is correct.

The run skips the look for a chip and drives everything else at a small
size on the CPU: set-up, warm-up, the window, the drain and the check,
with the cell's own limits.  Faults:

* a token altered where it is produced (the sampler's output);
* a decode step that returns its state unchanged (the KV pool);
* replay: an answer altered where it is produced (the cache read), a
  score altered where it is produced, and half of each chunk left out of
  the bootstrap partials, the mean taken over the rest;
* four replicas: the first token altered on one replica alone, which the
  check meets because it compares requests of every replica.

The exchange between chips is not a fault these cells can have: the
four-replica cell's replicas share nothing but the router.
"""

import dataclasses
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
from traffic import route

DATA = Path(__file__).resolve().parent / "data"
LIMITS = Path(harness.HERE) / "limits"


def _cell(config: str, mix: str, limits: str) -> harness.Cell:
    return harness.Cell(
        name=f"test.{mix}", chips=1,
        config=json.loads((DATA / config).read_text()),
        mix=json.loads((DATA / mix).read_text()),
        limits=json.loads((LIMITS / limits).read_text()),
        end_to_end=[{"name": "setup_s", "unit": "s"}], per_layer=[],
    )


def _run(cell: harness.Cell, seed: int = 2**31 + 99) -> dict:
    return harness.run_cell(
        cell, seed, 2.0, False, False, time.monotonic(), jax.devices(), None
    )


@pytest.fixture
def fewshot():
    return _cell("tiny-qwen3.json", "tiny-fewshot.json", "qwen3-4b.fewshot.json")


@pytest.fixture
def replay():
    return _cell("tiny-qwen3.json", "tiny-replay.json", "qwen3-4b.replay.json")


@pytest.fixture
def fewshot_x4():
    return _cell("tiny-qwen3.json", "tiny-fewshot-x4.json", "qwen3-4b.fewshot-x4.json")


def test_sound_runs_are_correct(fewshot, replay):
    for cell in (fewshot, replay):
        r = _run(cell)
        assert r["correct"], r["checks"]


def test_altered_token_is_caught(fewshot, monkeypatch):
    from repro.serve import steps

    orig = steps.greedy_sample

    def altered(logits, vocab_size):
        tok = orig(logits, vocab_size)
        return tok.at[0].set((tok[0] + 1) % vocab_size)

    monkeypatch.setattr(steps, "greedy_sample", altered)
    r = _run(fewshot)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_unchanged_decode_state_is_caught(fewshot, monkeypatch):
    from repro.serve.scheduler import ContinuousBatcher

    orig = ContinuousBatcher._paged_decode_impl

    def stale(self, params, tokens, pools, *rest):
        logits, _ = orig(self, params, tokens, pools, *rest)
        return logits, pools

    monkeypatch.setattr(ContinuousBatcher, "_paged_decode_impl", stale)
    r = _run(dataclasses.replace(
        fewshot, mix=dict(fewshot.mix, model={"max_tokens": 12, "temperature": 0.0})
    ))
    assert not r["correct"]


def test_altered_cached_answer_is_caught(replay, monkeypatch):
    from repro.core.cache import ResponseCache

    orig = ResponseCache.lookup

    def altered(self, key):
        hit = orig(self, key)
        if hit is not None:
            hit.response_text = hit.response_text + " extra"
        return hit

    monkeypatch.setattr(ResponseCache, "lookup", altered)
    r = _run(replay)
    assert not r["correct"]
    assert r["checks"]["text_mismatches"]["value"] > 0


def test_altered_score_is_caught(replay, monkeypatch):
    from repro.metrics import lexical

    orig = lexical.token_f1
    monkeypatch.setattr(
        lexical, "token_f1", lambda p, r: min(1.0, orig(p, r) + 1e-9)
    )
    monkeypatch.setitem(lexical._SCALAR, "token_f1", lexical.token_f1)
    r = _run(replay)
    assert not r["correct"]
    assert r["checks"]["score_mismatches"]["value"] > 0


def test_half_batch_in_the_bootstrap_is_caught(replay, monkeypatch):
    from repro.kernels.bootstrap import ops

    orig = ops.bootstrap_partials

    def half(scores, seed, start, **kw):
        scores = np.asarray(scores)
        swx, sw = orig(scores[: len(scores) // 2], seed, start, **kw)
        # the sums over the kept half, scaled as if they were the mean
        # over the whole chunk
        return swx * 2, sw * 2

    monkeypatch.setattr(ops, "bootstrap_partials", half)
    r = _run(replay)
    assert not r["correct"]
    assert r["checks"]["ci_gap"]["value"] > r["checks"]["ci_gap"]["limit"]



def test_sound_run_over_replicas_is_correct(fewshot_x4):
    r = _run(fewshot_x4)
    assert r["correct"], r["checks"]
    assert r["checks"]["replicas_short"]["value"] == 0
    assert r["window"]["compiles"] == 0


def test_altered_token_on_one_replica_is_caught(fewshot_x4, monkeypatch):
    from repro.core.engines import LocalJaxEngine
    from repro.serve.scheduler import ContinuousBatcher

    inf = fewshot_x4.mix["inference"]
    orig_submit = LocalJaxEngine._submit_locked
    orig_first = ContinuousBatcher._first_token

    def submit(self, request):
        rid = orig_submit(self, request)
        if route(request.prompt, inf["n_replicas"], inf["routing_prefix_len"]) == 3:
            self._scheduler.altered = True
        return rid

    def first(self, logits):
        tok = orig_first(self, logits)
        return (tok + 1) % self.cfg.vocab_size if getattr(self, "altered", False) else tok

    monkeypatch.setattr(LocalJaxEngine, "_submit_locked", submit)
    monkeypatch.setattr(ContinuousBatcher, "_first_token", first)
    r = _run(fewshot_x4)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]
