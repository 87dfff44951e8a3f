"""Work counts against counts worked out by hand at a reduced size, and
against the counts the benchmark's configurations read before the counts
moved into their families' modules."""

import json
from pathlib import Path

import pytest

import work
from families import _dense

CONFIGS = Path(work.__file__).resolve().parent / "configs"

# d=8, 2 layers, 2 heads of 4 (1 kv head), ff=16, vocab 10, bf16
CFG = {
    "model_type": "qwen3", "hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
    "vocab_size": 10, "torch_dtype": "bfloat16",
}


def test_layer_and_head_params():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    assert _dense.layer_params(CFG) == 64 + 32 + 32 + 64 + 384
    assert _dense.head_params(CFG) == 80
    assert work.weight_bytes(CFG) == (2 * 576 + 80) * 2


def test_token_flops_and_kv_bytes():
    # 2 flops per weight per layer, plus q.k and p.v: 4 * heads * dh * ctx
    assert work.token_flops(CFG, 3) == 2 * (2 * 576 + 4 * 2 * 4 * 3)
    # k and v, 2 layers, 1 kv head of 4, 2 bytes
    assert work.kv_bytes_per_position(CFG) == 2 * 2 * 1 * 4 * 2


def test_decode_step():
    flops, nbytes = work.decode_step(CFG, [3, 5])
    assert flops == work.token_flops(CFG, 3) + work.token_flops(CFG, 5) + 2 * 2 * 80
    kv = 32
    # weights once, live cache (2 + 4 positions) read, 2 new rows and
    # 2 embedding rows written/read
    assert nbytes == 2464 + 6 * kv + 2 * (kv + 8 * 2)


def test_prefill_after_shared_prefix():
    flops, nbytes = work.prefill(CFG, 5, 3)
    # rows 3 and 4 attend to 4 and 5 positions; one row of logits
    assert flops == work.token_flops(CFG, 4) + work.token_flops(CFG, 5) + 2 * 80
    assert nbytes == 2464 + 3 * 32 + 2 * (32 + 16)


def test_statistics_kernels():
    assert work.bootstrap_partials(16, 2, 10) == (2 * 2 * 10 * 16 * 2,
                                                  4 * (32 + 40))
    assert work.bertscore_pr([(3, 2), (1, 4)], dim=8) == (
        2 * 3 * 2 * 8 + 2 * 1 * 4 * 8, (5 + 5) * 8 * 4)


def test_roofline_picks_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert work.roofline_seconds(100.0, 50.0, peaks) == 5.0


#: what ``work.py`` counted for each configuration before families: weight
#: bytes, KV bytes a position, one token's FLOPs at context 300, a decode
#: step over contexts 300, 517, 1000 and 20, and prefills of 400 positions
#: after 256 shared and of 96 after none
PINNED = {
    "qwen3-4b.json": (
        8044544000, 147456, 7443578880,
        (33261682688.0, 8315441152.0),
        (1075073908736.0, 8104263680.0),
        (701120774144.0, 8059191296.0),
    ),
    "qwen2.5-32b-L6.json": (
        7408189440, 24576, 5887918080,
        (29858488320.0, 7453376512.0),
        (849921638400.0, 7419494400.0),
        (563830456320.0, 7411531776.0),
    ),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_of_the_benchmark_configs_are_unchanged(name):
    c = json.loads((CONFIGS / name).read_text())
    assert (
        work.weight_bytes(c), work.kv_bytes_per_position(c), work.token_flops(c, 300),
        work.decode_step(c, [300, 517, 1000, 20]), work.prefill(c, 400, 256),
        work.prefill(c, 96, 0),
    ) == PINNED[name]
    # a dense family reads nothing of the window
    assert work.decode_step(c, [300], object()) == work.decode_step(c, [300])
