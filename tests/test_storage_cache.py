"""DeltaLite (ACID log, time travel, CAS) and the 5-policy response cache."""

import gzip
import json
import os
import random
import sys
import threading

import jax
import pytest

from repro import obs
from repro.core import CacheEntry, CacheMiss, CachePolicy, ResponseCache
from repro.storage import ChunkManifest, DeltaLite


def _rows(lo, hi):
    return [{"prompt_hash": f"k{i}", "value": i} for i in range(lo, hi)]


def test_append_read_time_travel(tmp_path):
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")
    v0 = t.append(_rows(0, 3))
    v1 = t.append(_rows(3, 5))
    assert (v0, v1) == (0, 1)
    assert len(t.read()) == 5
    assert len(t.read(version=0)) == 3  # time travel
    assert t.latest_version() == 1
    hist = t.history()
    assert [h["version"] for h in hist] == [0, 1]


def test_lookup_cas_pruning(tmp_path):
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")
    t.append(_rows(0, 100))
    t.append([{"prompt_hash": "k5", "value": 999}])  # upsert: later wins
    assert t.lookup("k5")["value"] == 999
    assert t.lookup("k99")["value"] == 99
    assert t.lookup("missing") is None
    assert "k42" in t.keys()


def test_overwrite_and_compact(tmp_path):
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")
    t.append(_rows(0, 4))
    t.append([{"prompt_hash": "k1", "value": -1}])
    t.compact()
    rows = t.read()
    assert len(rows) == 4  # deduped latest-wins
    assert {r["value"] for r in rows if r["prompt_hash"] == "k1"} == {-1}
    # old version still readable (time travel survives compaction)
    assert len(t.read(version=0)) == 4

    t.overwrite([{"prompt_hash": "solo", "value": 0}])
    assert len(t.read()) == 1


def test_concurrent_appends_all_commit(tmp_path):
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")

    def writer(i):
        DeltaLite(str(tmp_path / "t"), key_column="prompt_hash").append(
            [{"prompt_hash": f"w{i}", "value": i}]
        )

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.read()) == 8
    assert t.latest_version() == 7  # optimistic concurrency: all distinct


def test_partial_write_invisible(tmp_path):
    """A segment without a log commit must not be observed (crash safety)."""
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")
    t.append(_rows(0, 2))
    # simulate a crashed writer: orphan segment file, no log entry
    with open(tmp_path / "t" / "data" / "part-orphan.jsonl.gz", "wb") as f:
        f.write(b"garbage")
    assert len(t.read()) == 2


def test_deterministic_version_race_retries_and_both_commit(tmp_path):
    """Two writers that observe the SAME latest version must race on the
    version file: exactly one wins os.link, the loser retries with the next
    version, and both rows land."""
    barrier = threading.Barrier(2, timeout=10)
    version_calls: dict[int, int] = {}

    class RacingDelta(DeltaLite):
        def latest_version(self):
            v = super().latest_version()
            me = threading.get_ident()
            version_calls[me] = version_calls.get(me, 0) + 1
            if version_calls[me] == 1:
                barrier.wait()  # both writers now commit the same version
            return v

    errors: list[Exception] = []

    def writer(i: int) -> None:
        try:
            RacingDelta(str(tmp_path / "t"), key_column="prompt_hash").append(
                [{"prompt_hash": f"w{i}", "value": i}]
            )
        except Exception as e:  # pragma: no cover - surfaced by assert below
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")
    assert t.latest_version() == 1
    assert {r["prompt_hash"] for r in t.read()} == {"w0", "w1"}
    # the race loser called latest_version a second time (the retry)
    assert sorted(version_calls.values()) == [1, 2]


def test_orphaned_valid_segment_invisible_everywhere(tmp_path):
    """A writer dying between segment write and log commit leaves a fully
    valid but unreferenced segment: readers, point lookups, key listings
    and time travel must never observe it."""
    t = DeltaLite(str(tmp_path / "t"), key_column="prompt_hash")
    t.append(_rows(0, 3))
    # crash exactly between _write_segment and _commit
    t._write_segment([{"prompt_hash": "ghost", "value": 666}])
    assert len(os.listdir(tmp_path / "t" / "data")) == 2  # file is on disk
    assert len(t.read()) == 3
    assert t.lookup("ghost") is None
    assert "ghost" not in t.keys()
    assert len(t.read(version=0)) == 3
    assert t.latest_version() == 0
    # and a later commit still doesn't resurrect it
    t.append(_rows(3, 4))
    assert t.lookup("ghost") is None
    assert len(t.read()) == 4


def test_chunk_manifest_isolation_and_latest_wins(tmp_path):
    m1 = ChunkManifest(str(tmp_path / "spill"), run_key="run-a")
    m2 = ChunkManifest(str(tmp_path / "spill"), run_key="run-b")
    m1.record(0, {"n_rows": 10})
    m2.record(0, {"n_rows": 99})
    assert m1.completed()[0]["n_rows"] == 10  # runs are isolated
    assert m2.completed()[0]["n_rows"] == 99
    m1.record(0, {"n_rows": 11})  # duplicate commit: latest wins
    assert m1.completed()[0]["n_rows"] == 11
    assert set(m1.completed()) == {0}


# ---------------------------------------------------------------------------
# the in-memory snapshot of the log: equal to re-reading it, at every version
# ---------------------------------------------------------------------------


def _reference(path, version):
    """The table at ``version`` read the plain way: every commit file up to
    it, then every live segment in commit order.  Returns (rows, key ->
    latest row)."""
    live = {}
    for v in range(version + 1):
        with open(os.path.join(path, "_log", f"{v:08d}.json")) as f:
            entry = json.load(f)
        for add in entry.get("add", []):
            live[add["file"]] = add
        for rm in entry.get("remove", []):
            live.pop(rm, None)
    rows = []
    for name in live:
        with gzip.open(os.path.join(path, "data", name), "rt") as f:
            rows.extend(json.loads(line) for line in f if line.strip())
    latest = {str(r["prompt_hash"]): r for r in rows}
    return rows, latest


def _assert_matches(t, path, version, pool):
    rows, latest = _reference(path, version)
    at = None if version == t.latest_version() else version
    assert t.read(version=at) == rows
    assert t.keys(version=at) == set(latest)
    for k in pool:
        assert t.lookup(k, version=at) == latest.get(k)


def test_snapshot_equals_a_replay_of_the_log_at_every_version(tmp_path):
    """Seeded interleaving of appends, conditional appends, overwrites and
    compactions from two handles (and segments without a key list): after
    each step, both handles and a fresh one answer read, keys and lookup at
    the latest and every earlier version as the log replayed from disk."""
    path = str(tmp_path / "t")
    rng = random.Random(20241)
    a = DeltaLite(path, key_column="prompt_hash")
    b = DeltaLite(path, key_column="prompt_hash")
    keyless = DeltaLite(path)
    pool = [f"k{i}" for i in range(10)] + ["missing"]
    for step in range(24):
        writer = rng.choice((a, b))
        rows = [
            {"prompt_hash": rng.choice(pool[:-1]), "value": 100 * step + i}
            for i in range(rng.randint(1, 4))
        ]
        op = rng.choice(
            ("append", "append", "if_absent", "unkeyed", "overwrite", "compact")
        )
        if op == "append":
            writer.append(rows)
        elif op == "if_absent":
            writer.append_if_absent(rows)
        elif op == "unkeyed":
            keyless.append(rows)
        elif op == "overwrite":
            writer.overwrite(rows)
        else:
            writer.compact()
        last = a.latest_version()
        for handle in (a, b, DeltaLite(path, key_column="prompt_hash")):
            for v in range(-1, last + 1):
                _assert_matches(handle, path, v, pool)


def _files_opened(trace_dir, fn):
    """Commit files opened during ``fn()``, by the ``storage.read_log``
    spans it recorded, and its result."""
    obs.clear()
    with jax.profiler.trace(trace_dir):
        out = fn()
    files = sum(
        s.attrs["files"] for s in obs.records().spans if s.name == "storage.read_log"
    )
    obs.clear()
    return files, out


@pytest.mark.parametrize("k", [1, 4])
def test_lookup_opens_only_the_commit_files_of_other_handles(tmp_path, k):
    trace = str(tmp_path / "trace")
    path = str(tmp_path / "t")
    reader = DeltaLite(path, key_column="prompt_hash")
    writer = DeltaLite(path, key_column="prompt_hash")
    for i in range(3):
        writer.append(_rows(4 * i, 4 * i + 4))
    assert _files_opened(trace, lambda: reader.lookup("k0"))[0] == 3
    files, row = _files_opened(trace, lambda: reader.lookup("k5"))
    assert (files, row) == (0, {"prompt_hash": "k5", "value": 5})
    for i in range(k):
        writer.append([{"prompt_hash": f"new{i}", "value": i}])
    files, row = _files_opened(trace, lambda: reader.lookup(f"new{k - 1}"))
    assert (files, row) == (k, {"prompt_hash": f"new{k - 1}", "value": k - 1})
    assert _files_opened(trace, lambda: reader.lookup("k11"))[0] == 0
    # time travel below the snapshot opens no log file and sees no newer row
    files, row = _files_opened(trace, lambda: reader.lookup("new0", version=2))
    assert (files, row) == (0, None)


def test_threaded_lookups_see_every_key_committed_before_they_start(tmp_path):
    """Eight reader threads on one handle look up keys while a writer
    thread appends through that handle and through its own: no lookup
    misses a key whose commit returned before the lookup began."""
    path = str(tmp_path / "t")
    shared = DeltaLite(path, key_column="prompt_hash")
    own = DeltaLite(path, key_column="prompt_hash")
    committed: list[str] = []
    done = threading.Event()
    errors: list[BaseException] = []

    def writer() -> None:
        try:
            for i in range(60):
                key = f"w{i}"
                (shared if i % 2 else own).append([{"prompt_hash": key, "value": i}])
                committed.append(key)
        except BaseException as e:  # pragma: no cover — the assertion target
            errors.append(e)
        finally:
            done.set()

    def reader(seed: int) -> None:
        rng = random.Random(seed)
        try:
            while not done.is_set():
                known = list(committed)
                if not known:
                    continue
                for key in (known[-1], rng.choice(known)):
                    row = shared.lookup(key)
                    assert row is not None and row["prompt_hash"] == key, key
                assert set(known) <= shared.keys()
        except BaseException as e:  # pragma: no cover — the assertion target
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader, args=(i,)) for i in range(8)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    assert shared.keys() == {f"w{i}" for i in range(60)}


def test_manifest_first_committer_wins_with_warm_snapshots(tmp_path):
    """Two manifest handles whose snapshots are warm race ``try_record`` on
    each chunk id: exactly one commits, and every handle reads its row."""
    root = str(tmp_path / "spill")
    m1 = ChunkManifest(root, "run")
    m2 = ChunkManifest(root, "run")
    m1.record(0, {"by": "m1"})
    assert m1.get(0)["by"] == m2.get(0)["by"] == "m1"  # both snapshots warm
    # a stale snapshot still loses: m2 has not read m1's commit of chunk 1
    assert m1.try_record(1, {"by": "m1"})
    assert not m2.try_record(1, {"by": "m2"})
    winners: dict[int, list[str]] = {}
    for ci in range(2, 12):
        barrier = threading.Barrier(2, timeout=10)
        won: list[str] = []

        def attempt(m, name, ci=ci, barrier=barrier, won=won):
            barrier.wait()
            if m.try_record(ci, {"by": name}):
                won.append(name)

        threads = [
            threading.Thread(target=attempt, args=(m, n))
            for m, n in ((m1, "m1"), (m2, "m2"))
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
        assert not any(th.is_alive() for th in threads)
        assert len(won) == 1, (ci, won)
        winners[ci] = won
    fresh = ChunkManifest(root, "run")
    for m in (m1, m2, fresh):
        done = m.completed()
        assert set(done) == set(range(12))
        for ci, (name,) in winners.items():
            assert done[ci]["by"] == name
    assert len(fresh.table.read()) == 12  # losers left no rows


# ---------------------------------------------------------------------------
# response cache policies
# ---------------------------------------------------------------------------


def _entry(key: str, text: str = "resp") -> CacheEntry:
    return CacheEntry(
        prompt_hash=key, model_name="m", provider="p", prompt_text="q",
        response_text=text, input_tokens=3, output_tokens=2,
        latency_ms=1.0, created_at=0.0,
    )


def test_enabled_policy(tmp_path):
    c = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    assert c.lookup("a") is None
    c.put([_entry("a")])
    assert c.lookup("a").response_text == "resp"
    assert c.stats()["hits"] == 1


def test_read_only_never_writes(tmp_path):
    c = ResponseCache(str(tmp_path / "c"), CachePolicy.READ_ONLY)
    c.put([_entry("a")])
    assert c.lookup("a") is None
    assert c.stats()["writes"] == 0


def test_write_only_never_reads(tmp_path):
    c = ResponseCache(str(tmp_path / "c"), CachePolicy.WRITE_ONLY)
    c.put([_entry("a")])
    assert c.lookup("a") is None
    c2 = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    assert c2.lookup("a") is not None  # warmed


def test_replay_raises_on_miss(tmp_path):
    warm = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    warm.put([_entry("a")])
    c = ResponseCache(str(tmp_path / "c"), CachePolicy.REPLAY)
    assert c.lookup("a") is not None
    with pytest.raises(CacheMiss):
        c.lookup("missing")


def test_ttl_expiry(tmp_path):
    c = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    e = _entry("a")
    e.ttl_days = 1
    e.created_at = 0.0  # 1970 — long expired
    c.put([e])
    assert c.lookup("a") is None


def test_cross_process_visibility(tmp_path):
    c1 = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    c2 = ResponseCache(str(tmp_path / "c"), CachePolicy.ENABLED)
    c1.put([_entry("a")])
    # c2 built before the write: refresh picks up the new version
    assert c2.lookup("a") is not None
