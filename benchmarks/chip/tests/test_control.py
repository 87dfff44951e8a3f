"""The controls come out not correct: with the reference computed one
precision lower (fp8) put in the program's place, the run's own verdict
reads false, while the program's verdict on the same run reads true.

Run at a size a test can hold, on the CPU: a 4-layer, 512-wide Qwen3 with
an 8192-row vocabulary under the few-shot mix, on one replica and on four,
and the replay mix over 256 cached responses, each on three seeds.
"""

import json
import time
from pathlib import Path

import jax
import pytest

import harness

DATA = Path(__file__).resolve().parent / "data"
LIMITS = Path(harness.HERE) / "limits"


def _run(config, mix, limits, seed):
    cell = harness.Cell(
        name="test.control", chips=1,
        config=json.loads((DATA / config).read_text()),
        mix=json.loads((DATA / mix).read_text()),
        limits=json.loads((LIMITS / limits).read_text()),
        end_to_end=[{"name": "setup_s", "unit": "s"}], per_layer=[],
    )
    return harness.run_cell(
        cell, seed, 2.0, False, True, time.monotonic(), jax.devices(), None
    )


@pytest.mark.parametrize("seed", [5, 2**31 + 6, 7])
def test_fp8_model_control_fails_the_logit_gap(seed):
    r = _run("small-qwen3.json", "tiny-fewshot.json", "qwen3-4b.fewshot.json", seed)
    assert r["program"]["correct"], r["program"]["checks"]
    assert not r["correct"], r["checks"]
    gap = r["program"]["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"] < r["checks"]["logit_gap"]["value"]
    assert r["checks"]["logit_gap"]["value"] == r["controls"]["logit_gap"]


@pytest.mark.parametrize("seed", [5, 2**31 + 6, 7])
def test_fp8_model_control_fails_the_logit_gap_over_replicas(seed):
    r = _run("small-qwen3.json", "tiny-fewshot-x4.json", "qwen3-4b.fewshot-x4.json", seed)
    assert r["program"]["correct"], r["program"]["checks"]
    assert not r["correct"], r["checks"]
    gap = r["program"]["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"] < r["checks"]["logit_gap"]["value"]
    assert r["program"]["checks"]["replicas_short"]["value"] == 0


@pytest.mark.parametrize("seed", [5, 2**31 + 6, 7])
def test_fp8_statistics_control_fails_a_gap(seed):
    """The control has to fail one of the cell's numbers, not each: at this
    size the CI's gap can stay under its limit while BERTScore's does not."""
    r = _run("tiny-qwen3.json", "tiny-replay.json", "qwen3-4b.replay.json", seed)
    own = r["program"]["checks"]
    assert r["program"]["correct"], own
    assert not r["correct"], r["checks"]
    names = ("bertscore_gap", "ci_gap")
    for name in names:
        assert own[name]["value"] <= own[name]["limit"], name
    assert any(r["checks"][n]["value"] > r["checks"][n]["limit"] for n in names)
    # half of every chunk left out of the bootstrap reads far above the limit
    assert r["faults"]["ci_gap.half_chunk"] > 3 * own["ci_gap"]["limit"], r["faults"]
