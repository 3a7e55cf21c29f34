"""Keyed CommPlan cache: repeated step signatures hit a precompiled plan.

The paper's persistent-kernel claim (§3.3) is that the schedule is decided
once and *reused*; this cache is where the reuse happens on our side.  The
key is everything the compiled schedule depends on — pytree signature
(treedef + per-leaf shape/dtype), policy fingerprint, axis names, device
count, collective kind — so any change that could alter the schedule
misses and recompiles, and everything else is a dict lookup instead of
re-running the bucketing/width/gating decision logic at trace time.

Hit/miss counters are exposed for tests and ``benchmarks/fig_sched.py``
(plan-cache hit rate is the benchmark's headline number).

Eviction: the store is an LRU bounded by ``capacity`` (``None`` =
unbounded).  Long-running sync/serve loops touch an open-ended stream of
signatures (every new cache shape / weight tree compiles a plan); without
a bound the process cache grows forever.  The default process cache is
bounded (``REPRO_PLAN_CACHE_CAP``, default 512 — far above any steady-state
working set, so eviction only fires on genuine signature churn);
``cache_info()`` surfaces hits/misses/evictions/size for tests and
benchmarks.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import pickle
import threading
from typing import Callable, Optional

from repro import obs
from repro.sched.plan import CommPlan


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    @property
    def compiles(self) -> int:
        return self.misses

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class PlanCache:
    """Thread-safe keyed LRU plan store with hit/miss/eviction accounting.

    ``capacity=None`` disables eviction (the pre-bound behaviour); a
    positive capacity evicts the least-recently-USED entry (hits refresh
    recency) when an insert would exceed it."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        if capacity is not None and capacity < 1:
            raise ValueError(f"capacity must be >= 1 or None, got {capacity}")
        self._plans: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self.capacity = capacity
        self.stats = CacheStats()

    def _evict_over_capacity_locked(self) -> None:
        while self.capacity is not None and len(self._plans) > self.capacity:
            self._plans.popitem(last=False)
            self.stats.evictions += 1

    def _obs_label(self) -> str:
        """Gauge label: the process cache is "default", private instances
        (tests, benchmarks) are "local" so they cannot stomp its series."""
        return "default" if self is globals().get("_DEFAULT") else "local"

    def _export_obs(self) -> None:
        """Mirror cache_info() into the metrics registry (no-op when off)."""
        if not obs.enabled():
            return
        label = self._obs_label()
        with self._lock:
            hits, misses = self.stats.hits, self.stats.misses
            evictions, size = self.stats.evictions, len(self._plans)
        obs.metric("plan_cache_hits").set(hits, cache=label)
        obs.metric("plan_cache_misses").set(misses, cache=label)
        obs.metric("plan_cache_evictions").set(evictions, cache=label)
        obs.metric("plan_cache_size").set(size, cache=label)

    def get_or_compile(self, key: tuple, builder: Callable[[], CommPlan]) -> CommPlan:
        """Return the plan for ``key``, compiling (and storing) on miss."""
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
                self.stats.hits += 1
        if plan is not None:
            obs.instant("plan_cache:hit", kind=getattr(plan, "kind", "?"),
                        cache=self._obs_label())
            self._export_obs()
            return plan
        # compile outside the lock: builders are pure and idempotent, so a
        # racing double-compile is wasted work, not a correctness issue
        with obs.span("plan_cache:compile", cache=self._obs_label()) as sp:
            plan = builder()
            sp.args["kind"] = getattr(plan, "kind", "?")
        with self._lock:
            self._plans.setdefault(key, plan)
            self._plans.move_to_end(key)
            self.stats.misses += 1
            self._evict_over_capacity_locked()
        self._export_obs()
        return plan

    def cache_info(self) -> dict:
        """Counter surface: hits/misses/evictions/size/capacity/hit_rate."""
        with self._lock:
            return {
                "hits": self.stats.hits,
                "misses": self.stats.misses,
                "evictions": self.stats.evictions,
                "size": len(self._plans),
                "capacity": self.capacity,
                "hit_rate": self.stats.hit_rate,
            }

    def plans(self) -> tuple:
        """Every stored plan, least recently used first."""
        with self._lock:
            return tuple(self._plans.values())

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key) -> bool:
        return key in self._plans

    def clear(self) -> None:
        """Drop every stored plan.  Lifetime hit/miss/eviction counters are
        NOT reset (clearing storage is not forgetting history — a monitor
        reading ``cache_info()`` across a clear must not see totals jump
        backwards); call :meth:`reset_stats` separately for a fresh ledger."""
        with self._lock:
            self._plans.clear()
        self._export_obs()

    def reset_stats(self) -> None:
        """Zero the hit/miss/eviction counters without touching the plans."""
        with self._lock:
            self.stats = CacheStats()
        self._export_obs()


# ---------------------------------------------------------------------------
# Plan persistence (ROADMAP "Plan-cache persistence"): CommPlans are pure
# hashable data — no arrays, no tracers — so a compiled schedule can be
# serialized next to a checkpoint and reloaded after a restart, carrying
# the decision work (bucketing, gating, eval_shape wire probes) across
# processes.  Keys travel inside the plans (``CommPlan.key`` IS the cache
# key it was compiled under), so the file is just a tuple of plans.
# ---------------------------------------------------------------------------

# v2: CommPlan grew the ``broadcast`` field (BroadcastSchedule) — files
# pickled before it exist would restore instances missing the attribute,
# so older versions are rejected rather than half-loaded
_PLANS_VERSION = 2


def save_plans(path: str, cache: "PlanCache" = None) -> int:
    """Serialize every plan in ``cache`` (default: the process cache) to
    ``path`` (atomic: tmp + rename).  Returns the number saved."""
    cache = default_cache() if cache is None else cache
    plans = cache.plans()
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "wb") as f:
        pickle.dump({"version": _PLANS_VERSION, "plans": plans}, f)
    os.replace(tmp, path)
    return len(plans)


def load_plans(path: str, cache: "PlanCache" = None, *,
               validate_backend: bool = True) -> int:
    """Load plans saved by :func:`save_plans` into ``cache`` (default: the
    process cache), keyed by each plan's own compile key.

    ``validate_backend`` (default) drops plans whose recorded kernel
    dispatch disagrees with the CURRENT backend probe — a schedule compiled
    on TPU must not replay compiled-Pallas dispatch on a CPU restart (the
    key would never be looked up anyway, since ``probe_backend()`` is part
    of every key; dropping keeps the cache free of dead entries).  Existing
    entries are never clobbered, and loading counts as neither hit nor
    miss.  Returns the number of plans inserted."""
    from repro.sched.compile import probe_backend

    with open(path, "rb") as f:
        payload = pickle.load(f)
    if payload.get("version") != _PLANS_VERSION:
        raise ValueError(f"unsupported plan-cache version in {path}: "
                         f"{payload.get('version')}")
    cache = default_cache() if cache is None else cache
    backend, use_pallas = probe_backend()
    loaded = 0
    with cache._lock:
        for plan in payload["plans"]:
            if validate_backend and (plan.backend, plan.use_pallas) != (
                    backend, use_pallas):
                continue
            if plan.key not in cache._plans:
                cache._plans[plan.key] = plan
                loaded += 1
        cache._evict_over_capacity_locked()
    return loaded


# The process-default cache: train/step, zero1, fsdp, serve and the sync
# engine all share it, so a step re-trace / re-publish with an unchanged
# signature is a guaranteed hit.  Bounded (LRU) so signature churn in
# long-running loops cannot leak; tests construct private PlanCache
# instances instead of clearing this one.
_DEFAULT = PlanCache(capacity=int(os.environ.get("REPRO_PLAN_CACHE_CAP",
                                                 "512")))


def default_cache() -> PlanCache:
    return _DEFAULT


def cache_stats() -> CacheStats:
    return _DEFAULT.stats


def cache_info() -> dict:
    """``cache_info()`` of the process-default plan cache."""
    return _DEFAULT.cache_info()
