"""DeltaLite: a log-structured ACID table with time travel and a CAS index.

The paper caches responses in Delta Lake for (a) ACID appends from many
executors, (b) time-travel reads for reproducing past evaluations, and
(c) efficient exact-key lookup.  No JVM exists on pod hosts, so we keep the
three *semantics* in ~300 lines (DESIGN.md §2):

* **segments**: immutable gzip'd JSON-lines files (columnar enough for our
  row sizes; zstd/Parquet is a drop-in swap on a real deployment),
* **transaction log**: ``_log/NNNNNNNN.json`` entries, one per commit,
  listing segment adds/removes.  Commits are atomic via ``O_CREAT|O_EXCL``
  on the next version file — optimistic concurrency: losers retry with the
  next version number (exactly Delta's protocol).  A commit file is
  published whole by a hard link and never rewritten or deleted, so each
  handle parses it at most once: the handle keeps the parsed entries and a
  snapshot of the table at the newest version it has seen, and a read at
  the latest version parses only the commit files newer than that
  snapshot (none while the table is unchanged).  Commits from other
  handles or processes are seen by the next read, as the log's own
  version decides,
* **time travel**: a read at version V replays the entries <= V (from the
  parsed entries the handle holds; never the newer snapshot),
* **CAS index**: each commit records the set of ``key_column`` values in
  its segments; the snapshot inverts them into key -> live segments, so a
  point lookup reads only the segments that can hold the key.

Crash safety: a writer dying after writing a segment but before its log
commit leaves an unreferenced file (invisible, garbage-collectable) — the
table never observes partial state.
"""

from __future__ import annotations

import gzip
import json
import os
import random
import threading
import time
import uuid
from typing import Callable, Iterable, TypeVar

from repro import obs


class CommitConflict(Exception):
    """Another writer committed this version first; retry."""


#: optimistic-concurrency retry budget.  Every lost race means another
#: writer committed (global progress), but a single writer can starve
#: under heavy contention — the budget plus jittered backoff below keeps
#: many concurrent chunk committers from spinning against each other.
COMMIT_RETRIES = 50


def _conflict_backoff(attempt: int) -> None:
    """Tiny jittered sleep after a lost version race: de-synchronizes
    writers that keep colliding on the same next-version number."""
    time.sleep(random.uniform(0.0, 0.002) * min(attempt + 1, 8))


T = TypeVar("T")


class _Snapshot:
    """The table at one version: its live segments in commit order and,
    for segments that list their keys, each key's live segments."""

    def __init__(self) -> None:
        self.version = -1
        self.live: dict[str, dict] = {}
        #: commit-order position of each live segment, for newest-first reads
        self.order: dict[str, int] = {}
        self.by_key: dict[str, set[str]] = {}
        #: live segments written without a key list: every lookup reads them
        self.unkeyed: set[str] = set()
        self._added = 0

    def fold(self, entry: dict) -> None:
        """Apply the next version's log entry."""
        for add in entry.get("add", []):
            name = add["file"]
            if name in self.live:
                self._unindex(self.live[name])
            else:
                self.order[name] = self._added
                self._added += 1
            self.live[name] = add
            self._index(add)
        for name in entry.get("remove", []):
            seg = self.live.pop(name, None)
            if seg is not None:
                self._unindex(seg)
                del self.order[name]
        self.version += 1

    def _index(self, seg: dict) -> None:
        if seg.get("keys") is None:
            self.unkeyed.add(seg["file"])
            return
        for k in seg["keys"]:
            self.by_key.setdefault(k, set()).add(seg["file"])

    def _unindex(self, seg: dict) -> None:
        if seg.get("keys") is None:
            self.unkeyed.discard(seg["file"])
            return
        for k in seg["keys"]:
            names = self.by_key[k]
            names.discard(seg["file"])
            if not names:
                del self.by_key[k]

    def segments(self) -> list[dict]:
        return list(self.live.values())

    def candidates(self, key: str) -> list[str]:
        """Live segments that may hold ``key``, newest first."""
        names = self.by_key.get(key, set()) | self.unkeyed
        return sorted(names, key=self.order.__getitem__, reverse=True)

    def keys(self) -> tuple[set[str], list[str]]:
        """Keys the live segments list, and the segments that list none."""
        return set(self.by_key), list(self.unkeyed)


class DeltaLite:
    def __init__(self, path: str, key_column: str | None = None):
        self.path = path
        self.key_column = key_column
        # monotone scan hint: versions are append-only, so latest_version
        # can resume from the last one seen instead of walking from 0 —
        # O(new versions) instead of O(all versions) per call, which keeps
        # concurrent committers from bunching up on long logs.  Benign
        # under races: the hint only ever lags the truth.
        self._version_hint = -1
        # parsed log entries (entry i is version i) and the table at the
        # newest of them; a commit file is immutable once linked, so neither
        # is ever invalidated, only extended.  The lock guards both; segment
        # reads happen outside it.
        self._lock = threading.Lock()
        self._entries: list[dict] = []
        self._snap = _Snapshot()
        os.makedirs(os.path.join(path, "_log"), exist_ok=True)
        os.makedirs(os.path.join(path, "data"), exist_ok=True)

    # -- log plumbing ---------------------------------------------------------

    def _log_dir(self) -> str:
        return os.path.join(self.path, "_log")

    def _version_path(self, v: int) -> str:
        return os.path.join(self._log_dir(), f"{v:08d}.json")

    def latest_version(self) -> int:
        """Highest contiguous committed version (-1 = empty table)."""
        v = self._version_hint
        while os.path.exists(self._version_path(v + 1)):
            v += 1
        self._version_hint = v
        return v

    def _catch_up(self, version: int | None) -> None:
        """Parse the commit files after the snapshot's version, up to
        ``version`` (the latest when None), and fold them in.  Caller holds
        the lock."""
        last = self.latest_version() if version is None else version
        first = self._snap.version + 1
        if last < first:
            return
        with obs.span("storage.read_log", files=last - first + 1):
            for v in range(first, last + 1):
                with open(self._version_path(v)) as f:
                    entry = json.load(f)
                self._entries.append(entry)
                self._snap.fold(entry)

    def _read_log(self, version: int | None = None) -> list[dict]:
        with self._lock:
            self._catch_up(version)
            last = self._snap.version if version is None else version
            return self._entries[: max(last + 1, 0)]

    def _at(self, version: int | None, query: Callable[[_Snapshot], T]) -> T:
        """``query`` of the table at ``version`` (the latest when None): on
        the snapshot, under the lock, when it is that version; else on a
        replay of the entries up to ``version``."""
        with self._lock:
            self._catch_up(version)
            if version is None or version == self._snap.version:
                return query(self._snap)
            entries = self._entries[: max(version + 1, 0)]
        past = _Snapshot()
        for entry in entries:
            past.fold(entry)
        return query(past)

    def _live_segments(self, version: int | None = None) -> list[dict]:
        return self._at(version, _Snapshot.segments)

    # -- writes -----------------------------------------------------------------

    def _write_segment(self, rows: list[dict]) -> dict:
        name = f"part-{uuid.uuid4().hex}.jsonl.gz"
        fpath = os.path.join(self.path, "data", name)
        with gzip.open(fpath, "wt") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
        seg = {"file": name, "rows": len(rows)}
        if self.key_column:
            seg["keys"] = sorted({str(r[self.key_column]) for r in rows})
        return seg

    def _commit(
        self, entry: dict, retries: int = COMMIT_RETRIES, precheck=None
    ) -> int | None:
        """Atomic commit: the fully-written entry is published with a hard
        link, so a concurrent reader can never observe a partial log file;
        losers of the version race get FileExistsError and retry.

        ``precheck(v)`` (optional) runs before each attempt against the
        table state at version ``v - 1`` — the state the successful link
        at ``v`` linearizes after; returning False abandons the commit
        (returns None).  Conditional appends build on this single copy of
        the publish protocol.
        """
        for attempt in range(retries):
            v = self.latest_version() + 1
            if precheck is not None and not precheck(v):
                return None
            entry["version"] = v
            entry["timestamp"] = time.time()
            tmp = self._version_path(v) + f".{uuid.uuid4().hex}.tmp"
            with open(tmp, "w") as f:
                json.dump(entry, f)
            try:
                os.link(tmp, self._version_path(v))
                return v
            except FileExistsError:
                _conflict_backoff(attempt)
                continue  # lost the race; re-read latest and retry
            finally:
                os.unlink(tmp)
        raise CommitConflict(f"could not commit after {retries} attempts")

    def append(self, rows: Iterable[dict]) -> int:
        """Append rows as one new segment; returns the committed version."""
        rows = list(rows)
        if not rows:
            return self.latest_version()
        seg = self._write_segment(rows)
        return self._commit({"add": [seg], "remove": []})

    def append_if_absent(
        self, rows: Iterable[dict], retries: int = COMMIT_RETRIES
    ) -> int | None:
        """First-committer-wins conditional append: commit the rows only if
        none of their ``key_column`` values are already live in the table.

        The absence check runs against the table state immediately preceding
        the version we try to claim, and the ``O_CREAT|O_EXCL``-style link is
        the linearization point: if another writer claims that version first
        we lose the race, re-read, and re-check — so two writers racing on
        the same key can never both commit it.  Returns the committed
        version, or ``None`` if a key was already taken (the written segment
        is unlinked; losers leave no garbage, even when the retry budget is
        exhausted).
        """
        assert self.key_column, "append_if_absent requires a key_column"
        rows = list(rows)
        if not rows:
            return self.latest_version()
        keys = {str(r[self.key_column]) for r in rows}
        if keys & self.keys():  # cheap fast path: skip the segment write
            return None
        seg = self._write_segment(rows)

        def absent(v: int) -> bool:
            return not (keys & self.keys(version=v - 1))

        version: int | None = None
        try:
            version = self._commit(
                {"add": [seg], "remove": []}, retries=retries, precheck=absent
            )
        finally:
            if version is None:  # lost the key race or exhausted retries
                os.unlink(os.path.join(self.path, "data", seg["file"]))
        return version

    def overwrite(self, rows: Iterable[dict]) -> int:
        """Replace the table contents (old versions stay readable)."""
        seg = self._write_segment(list(rows))
        current = [s["file"] for s in self._live_segments()]
        return self._commit({"add": [seg], "remove": current})

    def compact(self) -> int:
        """Merge all live segments into one (latest-wins on the key column)."""
        rows = self.read()
        if self.key_column:
            dedup: dict[str, dict] = {}
            for r in rows:
                dedup[str(r[self.key_column])] = r
            rows = list(dedup.values())
        seg = self._write_segment(rows)
        current = [s["file"] for s in self._live_segments()]
        return self._commit({"add": [seg], "remove": current})

    # -- reads --------------------------------------------------------------------

    def _read_segment(self, name: str) -> list[dict]:
        fpath = os.path.join(self.path, "data", name)
        with obs.span("storage.read_segment"), gzip.open(fpath, "rt") as f:
            return [json.loads(line) for line in f if line.strip()]

    def read(self, version: int | None = None) -> list[dict]:
        """Full scan at a version (time travel when ``version`` is given)."""
        rows: list[dict] = []
        for seg in self._live_segments(version):
            rows.extend(self._read_segment(seg["file"]))
        return rows

    def lookup(self, key: str, version: int | None = None) -> dict | None:
        """CAS point lookup: latest row whose key_column equals ``key``.
        Reads only the segments the key index names for ``key`` (and those
        without a key list), newest first; the first that holds the key
        gives its last matching row."""
        assert self.key_column, "lookup requires a key_column"
        key = str(key)
        for name in self._at(version, lambda snap: snap.candidates(key)):
            hit: dict | None = None
            for row in self._read_segment(name):
                if str(row[self.key_column]) == key:
                    hit = row
            if hit is not None:
                return hit
        return None

    def keys(self, version: int | None = None) -> set[str]:
        out, unkeyed = self._at(version, _Snapshot.keys)
        for name in unkeyed:
            out.update(str(r[self.key_column]) for r in self._read_segment(name))
        return out

    def history(self) -> list[dict]:
        """Commit log (version, timestamp, files added/removed)."""
        return [
            {
                "version": e["version"],
                "timestamp": e["timestamp"],
                "added": [a["file"] for a in e.get("add", [])],
                "removed": e.get("remove", []),
            }
            for e in self._read_log()
        ]
