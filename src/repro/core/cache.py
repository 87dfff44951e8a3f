"""Content-addressable response cache on DeltaLite (paper §3.2, Table 1)."""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

from repro import obs
from repro.core.config import CachePolicy, cache_key
from repro.storage.deltalite import DeltaLite


class CacheMiss(Exception):
    """Raised in REPLAY mode when a key is absent."""


@dataclasses.dataclass
class CacheEntry:
    prompt_hash: str
    model_name: str
    provider: str
    prompt_text: str
    response_text: str
    input_tokens: int
    output_tokens: int
    latency_ms: float
    created_at: float
    ttl_days: int | None = None

    def to_row(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_row(cls, row: dict) -> "CacheEntry":
        return cls(**{k: row.get(k) for k in cls.__dataclass_fields__})


class ResponseCache:
    """Five-policy cache; point lookups go through the DeltaLite CAS index.

    The table handle keeps the committed log in memory, key index included,
    and folds in other writers' commits on the next read, so a lookup costs
    one version check and the read of the segment that holds the key.

    One handle is safely shared by concurrent chunk workers: the
    hit/miss/write counters are guarded by a lock, so they never lose
    increments.  (DeltaLite guards its own snapshot, and appends are safe
    via optimistic concurrency.)
    """

    def __init__(self, path: str, policy: CachePolicy = CachePolicy.ENABLED):
        self.policy = policy
        self.table = DeltaLite(path, key_column="prompt_hash")
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.writes = 0

    @staticmethod
    def key_for(
        prompt: str, model_name: str, provider: str,
        temperature: float, max_tokens: int,
    ) -> str:
        return cache_key(prompt, model_name, provider, temperature, max_tokens)

    # -- policy-aware operations -------------------------------------------------

    def lookup(self, key: str) -> CacheEntry | None:
        if self.policy in (CachePolicy.DISABLED, CachePolicy.WRITE_ONLY):
            return None
        with obs.span("cache.lookup"):
            return self._lookup(key)

    def _lookup(self, key: str) -> CacheEntry | None:
        # no lock held: concurrent lookups must not serialize behind each
        # other's (or a writer's) disk I/O
        row = self.table.lookup(key)
        if row is None:
            if self.policy == CachePolicy.REPLAY:
                raise CacheMiss(
                    f"replay mode: {key[:12]}… not cached "
                    f"({len(self.table.keys())} entries present)"
                )
            with self._lock:
                self.misses += 1
            return None
        entry = CacheEntry.from_row(row)
        if entry.ttl_days is not None and entry.created_at is not None:
            age_days = (time.time() - entry.created_at) / 86_400.0
            if age_days > entry.ttl_days:
                with self._lock:
                    self.misses += 1
                return None
        with self._lock:
            self.hits += 1
        return entry

    def put(self, entries: list[CacheEntry]) -> int:
        """Cache entries per policy; returns how many were recorded."""
        if self.policy in (CachePolicy.DISABLED, CachePolicy.READ_ONLY,
                           CachePolicy.REPLAY):
            return 0
        if not entries:
            return 0
        # the append itself is already safe under DeltaLite's optimistic
        # concurrency; only the counter goes under the lock
        self.table.append([e.to_row() for e in entries])
        with self._lock:
            self.writes += len(entries)
        return len(entries)

    def stats(self) -> dict[str, Any]:
        version = self.table.latest_version()
        entries = len(self.table.keys(version=version))
        with self._lock:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "writes": self.writes,
                "hit_rate": self.hits / total if total else 0.0,
                "entries": entries,
                "version": version,
            }
