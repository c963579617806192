"""Generic thread-safe weighted-LRU cache building block.

Analog of the reference's ``common/cache/Cache.java`` (the CacheBuilder
family every higher-level cache — IndicesRequestCache, fielddata,
script — is built on): per-entry weigher, max-weight LRU eviction,
optional TTL, a removal listener carrying the removal reason, and a
stats readout (hits/misses/evictions/memory bytes).

Two integrations make this the ONLY sanctioned cache idiom in this
engine (``tools/check_ad_hoc_caches.py`` rejects raw dict-on-object
caches):

- **breakers** — an optional circuit breaker (an object from
  ``common/breakers.py`` or a child name resolved lazily against the
  installed service) is charged for every resident byte; when a put
  would trip it the cache first evicts its own LRU tail to make room
  and, failing that, skips caching instead of dying — memory pressure
  degrades hit rate, never correctness.
- **telemetry** — hit/miss/eviction counters stream into the metrics
  registry as ``cache.<name>.{hits,misses,evictions}`` (and
  ``lock_waits``, below) so ``_nodes/stats`` exposes every cache
  without bespoke plumbing.

The lock is an RLock around an OrderedDict, and a critical section holds
only pure-Python bookkeeping: the OrderedDict, the weight, the counts
and the breaker's charge and release, which stay atomic with the
eviction loop.  Removal listeners run under it too and must not
re-enter the cache or block.  Nothing that can give up the interpreter
lock or queue on another contended lock runs under it: the last
reference to a removed value (a device array's free is a call into the
runtime that gives the interpreter lock up, and every caller then
queues on this lock behind a holder waiting to get it back) and the
telemetry counters' increments (each takes its counter's lock) are
applied after the release (``_Section``).  ``cache.<name>.lock_waits``
counts the acquisitions that found the lock held.  ``clock`` is
injectable so TTL tests never sleep.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict
from typing import Callable, Optional

from opensearch_tpu.common.breakers import CircuitBreakingError
from opensearch_tpu.common.telemetry import metrics as _metrics

# removal reasons (RemovalNotification.RemovalReason analog)
EXPLICIT = "explicit"        # invalidate()/invalidate_all()/invalidate_if()
REPLACED = "replaced"        # put() over an existing key
EVICTED = "evicted"          # weight pressure pushed it out
EXPIRED = "expired"          # TTL ran out


def estimate_weight(obj) -> int:
    """Cheap recursive byte estimate for cache weighers: exact for
    bytes/str/ndarray-likes, structural for containers, 8 for scalars.
    Deliberately NOT sys.getsizeof — device arrays report their buffer
    via ``nbytes``, which is the number that matters for budgets."""
    if obj is None:
        return 8
    nbytes = getattr(obj, "nbytes", None)
    if nbytes is not None:               # numpy / jax arrays
        return int(nbytes)
    if isinstance(obj, (bytes, bytearray)):
        return len(obj)
    if isinstance(obj, str):
        return 2 * len(obj) + 40
    if isinstance(obj, (int, float, bool)):
        return 8
    if isinstance(obj, dict):
        return 64 + sum(estimate_weight(k) + estimate_weight(v)
                        for k, v in obj.items())
    if isinstance(obj, (list, tuple, set, frozenset)):
        return 56 + sum(estimate_weight(v) for v in obj)
    import sys
    try:
        return sys.getsizeof(obj)
    except TypeError:
        return 64


def _default_weigher(key, value) -> int:
    return estimate_weight(key) + estimate_weight(value)


class _Entry:
    __slots__ = ("value", "weight", "expiry")

    def __init__(self, value, weight: int, expiry: Optional[float]):
        self.value = value
        self.weight = weight
        self.expiry = expiry


# the cache's telemetry counters, in the order _Section.__exit__ reads them
_COUNTED = ("lock_waits", "hits", "misses", "evictions")


class _Section:
    """``with cache._section:`` — one critical section of ``cache``.

    Entering tries the lock first; a failed try is one ``lock_waits``
    and then a blocking acquire.  Leaving takes, still under the lock,
    the entries ``_remove`` set aside and the counts not yet reported,
    releases the lock, and only then increments the telemetry counters
    and drops the removed entries — so no value is freed, and no
    counter's lock taken, with the cache's lock held.  Its state lives
    on the cache and is touched only by the holder, so one instance
    serves every thread; each exit reports what no exit has yet."""

    __slots__ = ("_cache",)

    def __init__(self, cache: "Cache"):
        self._cache = cache

    def __enter__(self):
        lock = self._cache._lock
        if not lock.acquire(blocking=False):
            lock.acquire()
            self._cache._lock_waits += 1

    def __exit__(self, *exc) -> None:
        c = self._cache
        counts = (c._lock_waits, c._hits, c._misses, c._evictions)
        deltas = [n - r for n, r in zip(counts, c._reported)]
        c._reported = counts
        removed, c._removed = c._removed, []
        c._lock.release()
        for what, n in zip(_COUNTED, deltas):
            if n:
                _metrics().counter(f"cache.{c.name}.{what}").inc(n)  # metric-name-ok: bounded set of cache names
        del removed                  # the last references, lock released


class Cache:
    """Thread-safe weighted LRU cache.

    ``breaker``: a ``CircuitBreaker`` object, or a child name
    ("fielddata"/"request"/"in_flight") resolved against the INSTALLED
    breaker service at charge time (so tests that install() a sized
    service are honored).  ``max_weight=None`` disables weight eviction
    (the breaker still bounds residency).
    """

    def __init__(self, name: str, *,
                 max_weight: Optional[int] = None,
                 weigher: Optional[Callable] = None,
                 ttl_s: Optional[float] = None,
                 removal_listener: Optional[Callable] = None,
                 breaker=None,
                 clock: Callable[[], float] = time.monotonic):
        self.name = name
        self.max_weight = max_weight
        self.weigher = weigher or _default_weigher
        self.ttl_s = ttl_s
        self.removal_listener = removal_listener
        self._breaker_ref = breaker
        self._clock = clock
        self._lock = threading.RLock()
        self._section = _Section(self)
        self._entries: "OrderedDict" = OrderedDict()
        self._weight = 0
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._rejections = 0
        self._lock_waits = 0
        self._reported = (0, 0, 0, 0)   # _COUNTED as the counters have them
        self._removed: list = []        # removed entries, freed after release
        # registered now so a window's delta reads 0, not absent
        _metrics().counter(f"cache.{name}.lock_waits")  # metric-name-ok: bounded set of cache names

    # -- breaker plumbing --------------------------------------------------

    def _breaker(self):
        ref = self._breaker_ref
        if isinstance(ref, str):
            from opensearch_tpu.common.breakers import breaker_service
            return getattr(breaker_service(), ref)
        return ref

    def _charge(self, weight: int) -> bool:
        breaker = self._breaker()
        if breaker is None:
            return True
        try:
            breaker.add_estimate(weight, label=f"cache.{self.name}")
            return True
        except CircuitBreakingError:
            return False

    def _release(self, weight: int) -> None:
        breaker = self._breaker()
        if breaker is not None:
            breaker.release(weight)

    # -- internals (call inside a section) ----------------------------------

    def _remove(self, key, reason: str) -> Optional[_Entry]:
        """Take ``key`` out and hand its entry back; the entry is also
        set aside for ``_Section`` to drop after the release."""
        entry = self._entries.pop(key, None)
        if entry is None:
            return None
        self._removed.append(entry)
        self._weight -= entry.weight
        self._release(entry.weight)
        if reason == EVICTED:
            self._evictions += 1
        if self.removal_listener is not None:
            self.removal_listener(key, entry.value, reason)
        return entry

    def _evict_lru(self) -> bool:
        if not self._entries:
            return False
        key = next(iter(self._entries))
        self._remove(key, EVICTED)
        return True

    # -- public API --------------------------------------------------------

    def get(self, key, default=None):
        with self._section:
            entry = self._entries.get(key)
            if entry is not None and entry.expiry is not None \
                    and self._clock() >= entry.expiry:
                self._remove(key, EXPIRED)
                entry = None
            if entry is None:
                self._misses += 1
                return default
            self._entries.move_to_end(key)
            self._hits += 1
            return entry.value

    def get_or_load(self, key, loader: Callable):
        """Compute-if-absent.  The loader runs OUTSIDE the lock, so two
        racing callers may both compute (last write wins) — correct for
        derived data, which is all a cache may hold."""
        sentinel = object()
        value = self.get(key, sentinel)
        if value is not sentinel:
            return value
        value = loader()
        self.put(key, value)
        return value

    def put(self, key, value) -> bool:
        """Insert; returns False when the entry could not be admitted
        (single entry over max_weight, or the breaker refused even after
        evicting the whole cache)."""
        weight = int(self.weigher(key, value))
        with self._section:
            self._remove(key, REPLACED)
            if self.max_weight is not None and weight > self.max_weight:
                self._rejections += 1
                return False
            # make room under the breaker by shedding our own LRU tail
            # before giving up — OTHER components' memory is not ours to
            # evict, so a still-tripping breaker means "don't cache"
            while not self._charge(weight):
                if not self._evict_lru():
                    self._rejections += 1
                    return False
            expiry = (self._clock() + self.ttl_s
                      if self.ttl_s is not None else None)
            self._entries[key] = _Entry(value, weight, expiry)
            self._weight += weight
            if self.max_weight is not None:
                while self._weight > self.max_weight:
                    self._evict_lru()
            return True

    def invalidate(self, key) -> None:
        with self._section:
            self._remove(key, EXPLICIT)

    def invalidate_all(self) -> None:
        with self._section:
            for key in list(self._entries):
                self._remove(key, EXPLICIT)

    def invalidate_if(self, pred: Callable) -> int:
        """Remove every entry where ``pred(key, value)`` is true;
        returns the number removed (targeted invalidation — e.g. one
        index's request-cache entries)."""
        with self._section:
            doomed = [k for k, e in self._entries.items()
                      if pred(k, e.value)]
            for key in doomed:
                self._remove(key, EXPLICIT)
            return len(doomed)

    def set_max_weight(self, max_weight: Optional[int]) -> None:
        """Dynamic resize; shrinking evicts immediately."""
        with self._section:
            self.max_weight = max_weight
            if max_weight is not None:
                while self._weight > max_weight:
                    if not self._evict_lru():
                        break

    def entries(self) -> list[tuple]:
        """Snapshot of (key, value, weight), LRU→MRU (stats walks)."""
        with self._section:
            return [(k, e.value, e.weight)
                    for k, e in self._entries.items()]

    def __len__(self) -> int:
        with self._section:
            return len(self._entries)

    @property
    def weight(self) -> int:
        return self._weight

    def stats(self) -> dict:
        with self._section:
            return {"entries": len(self._entries),
                    "memory_size_in_bytes": self._weight,
                    "hit_count": self._hits,
                    "miss_count": self._misses,
                    "evictions": self._evictions,
                    "rejections": self._rejections}


def attached_cache(owner, attr: str, *, name: str,
                   max_weight: Optional[int] = None,
                   weigher: Optional[Callable] = None,
                   breaker=None) -> Cache:
    """Get-or-create a bounded ``Cache`` stored as ``owner.<attr>`` —
    the sanctioned replacement for the ``getattr(obj, "_x_cache") or
    obj._x_cache = {}`` idiom.  A weakref finalizer releases the
    cache's breaker reservation when the owner dies, so per-segment /
    per-searcher caches can never leak accounted bytes."""
    cache = getattr(owner, attr, None)
    if cache is None:
        cache = Cache(name, max_weight=max_weight, weigher=weigher,
                      breaker=breaker)
        try:
            weakref.finalize(owner, cache.invalidate_all)
        except TypeError:
            pass                 # owner not weakref-able: best effort
        setattr(owner, attr, cache)
    return cache
