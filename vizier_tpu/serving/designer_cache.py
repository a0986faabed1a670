"""Per-study designer-state cache with TTL/LRU eviction.

Each entry holds the LIVE designer (jit caches, trained GP fit, rng state
and all) plus the last trained unconstrained ARD params, so a steady-state
suggest pays an incremental update + warm-started train instead of a full
replay + cold multi-restart ARD. Entries are keyed by study resource name.

Eviction:
- **TTL** — an entry idle longer than ``ttl_seconds`` is dropped on the
  next cache access (lazy; there is no background reaper thread to leak);
- **LRU** — inserting beyond ``max_entries`` evicts the least recently
  used entry;
- **invalidation** — ``DeleteStudy`` calls :meth:`invalidate` so a reused
  study name never sees a predecessor's designer state.

Thread safety: the cache dict is guarded by one mutex; each entry carries
its own lock that callers hold across the designer's update→suggest
critical section, so suggests for *different* studies run concurrently
while suggests for one study serialize on its entry (the designer is
stateful).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, List, Optional, Set

from vizier_tpu.observability import tracing as tracing_lib
from vizier_tpu.serving import stats as stats_lib


class CachedDesignerEntry:
    """One study's live serving state."""

    def __init__(self, study_name: str, designer: Any, now: float):
        self.study_name = study_name
        self.designer = designer
        # Last trained unconstrained ARD params (whatever pytree the
        # designer's ``warm_start_state()`` returns); None until the first
        # trained suggest.
        self.warm_params: Any = None
        # Scalable-surrogate mirror (vizier_tpu.surrogates): the active
        # exact/sparse mode, kept in lock-step with the live designer by the
        # serving policy. The last trained sparse posterior (inducing set +
        # factorization) is the designer's own, read on demand
        # (``designer.sparse_inducing_state()``), and dies with the entry:
        # DeleteStudy invalidation drops it along with everything else.
        self.surrogate_mode: Any = None
        # Speculative pre-compute slot (vizier_tpu.serving.speculative): a
        # parked next-suggestion batch for one exact frontier fingerprint,
        # swapped atomically under the engine's serve lock (never under
        # this entry's designer lock — a slot pop must not wait behind an
        # in-flight live compute). Dies with the entry on invalidation.
        self.speculative: Any = None
        # Completed-trial ids already fed to the designer (incremental
        # updates only hand over the delta).
        self.incorporated_trial_ids: Set[int] = set()
        self.lock = threading.Lock()
        self.created_at = now
        self.last_used_at = now
        self.num_suggests = 0


class DesignerStateCache:
    """TTL/LRU cache: study resource name → :class:`CachedDesignerEntry`."""

    def __init__(
        self,
        max_entries: int = 64,
        ttl_seconds: float = 3600.0,
        stats: Optional[stats_lib.ServingStats] = None,
        time_fn: Callable[[], float] = time.monotonic,
        observe_latency: bool = True,
    ):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}.")
        self._max_entries = max_entries
        self._ttl = ttl_seconds
        self._stats = stats or stats_lib.ServingStats()
        self._time = time_fn
        # Lookup latency histogram: a miss pays designer construction (jit
        # compile caches and all) — exactly the cost the cache exists to
        # amortize, so it is worth a distribution, not just a counter.
        registry = getattr(self._stats, "registry", None)
        self._lookup_hist = (
            registry.histogram(
                "vizier_designer_cache_lookup_seconds",
                help="Designer-cache lookup wall time; a miss includes "
                "designer construction.",
            )
            if observe_latency and registry is not None
            else None
        )
        self._lock = threading.Lock()
        # Ordered oldest-used first; move_to_end on every hit.
        self._entries: "collections.OrderedDict[str, CachedDesignerEntry]" = (
            collections.OrderedDict()
        )
        # study name -> last-seen StudyConfig hash (note_config_hash).
        # Bounded independently of the entry map: the hash is what DETECTS
        # a delete/recreate turnover, so it must outlive the entry's own
        # TTL/LRU eviction, but million-study churn must not grow it
        # without bound.
        self._config_hashes: "collections.OrderedDict[str, str]" = (
            collections.OrderedDict()
        )
        self._max_hashes = max(1024, 16 * max_entries)

    @property
    def stats(self) -> stats_lib.ServingStats:
        return self._stats

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, study_name: str) -> bool:
        with self._lock:
            return study_name in self._entries

    def get_or_create(
        self, study_name: str, designer_factory: Callable[[], Any]
    ) -> CachedDesignerEntry:
        """The study's entry, creating (and possibly evicting) as needed.

        The designer factory runs OUTSIDE the cache mutex — constructing a
        GP designer compiles converters and optimizers, and holding the
        map lock through that would serialize unrelated studies' misses.
        The small race (two threads miss the same study concurrently) is
        resolved by a second lookup before insert: the loser's designer is
        discarded and the winner's entry returned.
        """
        t0 = time.perf_counter()
        now = self._time()
        # Counter/histogram updates run OUTSIDE the map mutex throughout:
        # they take the metrics registry's own locks, and nesting those
        # under the cache mutex would serialize unrelated studies' lookups
        # on metric bookkeeping (the lock_order pass keeps this mutex a
        # leaf of the serving lock graph).
        ttl_evicted = False
        with self._lock:
            entry = self._entries.get(study_name)
            if entry is not None and self._expired(entry, now):
                del self._entries[study_name]
                ttl_evicted = True
                entry = None
            if entry is not None:
                entry.last_used_at = now
                self._entries.move_to_end(study_name)
        if ttl_evicted:
            self._stats.increment("cache_evictions_ttl")
        if entry is not None:
            self._stats.increment("cache_hits")
            self._observe_lookup("hit", t0)
            return entry
        designer = designer_factory()
        lru_evictions = 0
        race_hit = False
        with self._lock:
            entry = self._entries.get(study_name)
            if entry is not None and not self._expired(entry, self._time()):
                # Lost the miss race; serve the winner's entry as a hit.
                entry.last_used_at = self._time()
                self._entries.move_to_end(study_name)
                race_hit = True
            else:
                entry = CachedDesignerEntry(study_name, designer, self._time())
                self._entries[study_name] = entry
                self._entries.move_to_end(study_name)
                while len(self._entries) > self._max_entries:
                    self._entries.popitem(last=False)
                    lru_evictions += 1
        if race_hit:
            self._stats.increment("cache_hits")
            self._observe_lookup("hit", t0)
            return entry
        self._stats.increment("cache_misses")
        if lru_evictions:
            self._stats.increment("cache_evictions_lru", lru_evictions)
        self._observe_lookup("miss", t0)
        return entry

    def _observe_lookup(self, result: str, t0: float) -> None:
        seconds = time.perf_counter() - t0
        if self._lookup_hist is not None:
            self._lookup_hist.observe(seconds, result=result)
        tracing_lib.add_current_event(
            "designer_cache", result=result, seconds=round(seconds, 6)
        )

    def peek(
        self, study_name: str, touch: bool = True
    ) -> Optional[CachedDesignerEntry]:
        """The study's live entry, or None — never constructs a designer.

        The speculative engine's lookup shape: parking or popping a
        pre-computed batch must not build designer state for a study
        nobody is serving. ``touch`` refreshes TTL/LRU (a served hit is a
        real use); ``touch=False`` is a pure inspection read.
        """
        now = self._time()
        with self._lock:
            entry = self._entries.get(study_name)
            if entry is None:
                return None
            if self._expired(entry, now):
                del self._entries[study_name]
                expired = True
            else:
                expired = False
                if touch:
                    entry.last_used_at = now
                    self._entries.move_to_end(study_name)
        if expired:
            self._stats.increment("cache_evictions_ttl")
            return None
        return entry

    def note_config_hash(self, study_name: str, config_hash: str) -> bool:
        """Pins the study's cached designer state to one config incarnation.

        A shared compute tier serves MANY frontends: a study can be
        deleted and recreated (same resource name, different search space)
        through a frontend whose ``DeleteStudy`` invalidation never
        reaches this process — there is no invalidation RPC on the Pythia
        surface. The servicer calls this with the request's parsed-config
        hash on every suggest; a hash TURNOVER (a different hash for a
        name we have seen) drops the stale entry so the next lookup
        builds a designer for the current incarnation. Returns True when
        a turnover was detected.
        """
        turned_over = False
        removed = None
        with self._lock:
            previous = self._config_hashes.get(study_name)
            self._config_hashes[study_name] = config_hash
            self._config_hashes.move_to_end(study_name)
            while len(self._config_hashes) > self._max_hashes:
                self._config_hashes.popitem(last=False)
            if previous is not None and previous != config_hash:
                turned_over = True
                removed = self._entries.pop(study_name, None)
        if removed is not None:
            self._stats.increment("cache_invalidations_config")
        return turned_over

    def invalidate(self, study_name: str) -> bool:
        """Drops the study's entry (study deleted / state known stale)."""
        with self._lock:
            removed = self._entries.pop(study_name, None)
        if removed is not None:
            self._stats.increment("cache_invalidations")
        return removed is not None

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def study_names(self) -> List[str]:
        """Cached studies, least recently used first (for inspection)."""
        with self._lock:
            return list(self._entries)

    def _expired(self, entry: CachedDesignerEntry, now: float) -> bool:
        return self._ttl > 0 and (now - entry.last_used_at) > self._ttl
