"""A traced run of a serving cell stops, rather than reporting per-layer
metrics off half the work, when a scheduler hook it reads is gone or a
window records decode steps and no prefills."""

import json
import types
from pathlib import Path

import pytest

import harness
from probes import Probes

DATA = Path(__file__).resolve().parent / "data"


class _Scheduler:
    n_slots = 1
    slot_pos = [0]
    slot_free = [True]

    def submit(self, req):
        return req

    def drain_completions(self):
        return []

    def step(self):
        return 0

    def _prefill_chunks(self, row, ptoks, start):
        return None


def _without(attr):
    return type("Renamed", (_Scheduler,), {attr: property(_absent)})()


def _absent(self):
    raise AttributeError


@pytest.mark.parametrize("attr", ["_prefill_chunks", "step", "slot_pos"])
def test_traced_serving_run_stops_on_a_missing_hook(attr):
    with pytest.raises(RuntimeError, match=attr):
        Probes(trace=True).attach_scheduler(_without(attr), 0)


def test_untraced_or_replay_runs_need_no_hook():
    Probes(trace=False).attach_scheduler(_without("_prefill_chunks"), 0)
    Probes(trace=True).attach_scheduler(_without("_prefill_chunks"), 0, serving=False)


def _ctx(steps, prefills):
    probes = Probes(trace=True)
    probes.steps = steps
    probes.prefills = prefills
    config = json.loads((DATA / "tiny-qwen3.json").read_text())
    window = types.SimpleNamespace(t0=0.0, t1=10.0, seconds=10.0)
    return harness.ReadContext(
        config, {}, {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}, window, probes, [],
    )


@pytest.mark.parametrize("metric", ["mfu", "prefill_roofline"])
def test_readers_refuse_steps_without_prefills(metric):
    ctx = _ctx([(1.0, 1.1, [40])], [])
    ctx.events = [{"ph": "X"}]
    with pytest.raises(RuntimeError, match="prefill"):
        harness.load_reader(metric).read(ctx)


def test_mfu_counts_both_steps_and_prefills():
    ctx = _ctx([(1.0, 1.1, [40])], [(2.0, 2.1, 32, 0)])
    assert harness.load_reader("mfu").read(ctx) > 0
