"""The traffic generator over replicas: the four-replica few-shot mix pins
task ``i`` to replica ``i mod 4`` on every seed and warms every page-count
shape on every replica, while a mix that names no replicas makes the rows
it made before replicas were a parameter."""

import hashlib
import itertools
import json
from pathlib import Path

import pytest

from tokenizer import n_tokens, words
from traffic import Mix, route

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEEDS = [7, 2**31 + 6, 3141592653]
PAGE = 16


def _spec(name: str) -> dict:
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()[:32]


#: (warm-up rows of every task, first 80 window rows of every task) as the
#: generator made them before it took replicas, by mix and seed
PINNED = {
    ("fewshot", 7): ("8fedd43a0217ef0c21d0c203b5a10881", "2187ff080048f958e02d17d822848f45"),
    ("fewshot", 2**31 + 6): ("c3e32dbad8702ae2f6f69e72f0cae962",
                             "f62066ce1ec0344e139872ae93651d50"),
    ("generative", 7): ("82d7dde2c6f1fa9362f4cad5ba106065",
                        "edb67bbc8bf1e57c819027939817c6da"),
    ("generative", 2**31 + 6): ("a55ded9b2879a967f357f6bcea4cb146",
                                "6f85b5c4d008c71c014f0563638c4911"),
    ("replay", 7): ("523944f3a4dde0a0caf147686feac854", "fa0ed78a5a3669a10f28cb863ebaae7a"),
    ("replay", 2**31 + 6): ("adc111140d9178d886b2f0cf0310ef79",
                            "871917fea4777641eded569e93cb42f8"),
}


@pytest.mark.parametrize("mix,seed", sorted(PINNED))
def test_one_replica_mixes_make_the_rows_they_made_before(mix, seed):
    spec = _spec(mix)
    m = Mix(spec, seed)
    n = len(spec["tasks"])
    warm = [m.warmup_rows(i, PAGE) for i in range(n)]
    window = [list(itertools.islice(m.stream(i), 80)) for i in range(n)]
    assert (_digest(warm), _digest(window)) == PINNED[(mix, seed)]


def test_route_is_the_program_router():
    from repro.core.service import ReplicaRouter, ReplicaView

    router = ReplicaRouter("prefix_affinity", prefix_len=64)
    for n in (2, 3, 4):
        views = [ReplicaView(i, 0, 0) for i in range(n)]
        for k in range(200):
            prompt = f"Q : shot {k} " * (k % 9 + 1) + "é"
            assert route(prompt, n, 64) == router.route(prompt, views)


@pytest.mark.parametrize("seed", SEEDS)
def test_x4_pins_task_i_to_replica_i_mod_4(seed):
    spec = _spec("fewshot-x4")
    m = Mix(spec, seed)
    assert m.n_replicas == 4 and len(spec["tasks"]) == 32
    lengths = [[] for _ in range(4)]
    for i, task in enumerate(spec["tasks"]):
        lengths[i % 4].append(task["header_tokens"])
        for row in itertools.islice(m.stream(i), 70):
            assert m.replica_of(m.prompt(row)) == i % 4, (i, row["uid"])
        assert n_tokens(m.headers[i]) == task["header_tokens"]
    # each replica holds one task of each of the eight header lengths
    assert all(sorted(x) == sorted(set(lengths[0])) for x in lengths)
    assert len(set(lengths[0])) == 8


@pytest.mark.parametrize("seed", SEEDS)
def test_x4_warms_every_page_count_on_every_replica(seed):
    spec = _spec("fewshot-x4")
    m = Mix(spec, seed)
    top = spec["engine"]["max_len"] // 2 // PAGE
    warm = [m.warmup_rows(i, PAGE) for i in range(len(spec["tasks"]))]
    uids = [r["uid"] for rows in warm for r in rows]
    assert len(uids) == len(set(uids))
    for i, rows in enumerate(warm):
        assert {m.replica_of(m.prompt(r)) for r in rows} == {i % 4}
    for r in range(4):
        sweep = [row for row in warm[r] if row["header"] != m.headers[r]]
        # prompts with a fresh prefix of every count of pages
        fresh = {n_tokens(m.prompt(row)) for row in sweep}
        assert {n * PAGE for n in range(1, top + 1)} <= fresh
        # and prompts sharing every count of pages with the longest of them
        longest = max(sweep, key=lambda row: n_tokens(m.prompt(row)))
        lw = words(longest["header"])
        shared = {
            (len(words(row["header"])) + 1) // PAGE for row in sweep
            if row["header"] and words(row["header"]) == lw[: len(words(row["header"]))]
            and (len(words(row["header"])) + 1) % PAGE == 0
            and row is not longest
        }
        assert set(range(1, top)) <= shared
    # tasks past the first of each replica warm only their body pages
    assert all(len(rows) == len(warm[4]) for rows in warm[5:])
