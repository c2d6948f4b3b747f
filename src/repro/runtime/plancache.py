"""Batch-bucketed plan + compiled-executor cache (the runtime's memo layer).

Every execution surface used to rebuild its :class:`PipelinePlan` and retrace
the fused jit for every distinct batch size (``DeployedKAN.replan`` per call,
``ServeEngine`` per prompt length).  This module centralizes that:

  * **bucketing** — a logical batch ``b`` is rounded up to the next power of
    two (:func:`bucket_batch`); inputs are zero-padded to the bucket and the
    output sliced back.  Rows are independent through the whole datapath
    (the MAC contracts the feature axis only), so padding is bit-invisible
    to the real rows.  A ragged request stream therefore compiles O(log B)
    executor variants instead of O(#distinct batch sizes).

  * **LRU cache** — ``(dims, specs, bucket, residual_raw, interpret,
    backend, flags) -> (PipelinePlan, compiled apply)``.  The compiled apply
    is a per-entry ``jax.jit`` closure over the static plan, so evicting an
    entry releases its executable.  Backend-specific statics (e.g. the acim
    :class:`~repro.core.cim.CIMConfig`, whose sigmas are baked into the
    traced program) ride in ``flags``.

  * **observability** — hit/miss/trace counters (`stats`), used by the
    recompile-count tests and the benchmark's cache report.  ``traces``
    increments inside the jitted python body, i.e. exactly once per real
    retrace, which is what the ragged-batch test asserts on.

  * **tuned tile plans** — ``repro.tune.tiles`` registers measured
    ``(bb, bo, bf)`` winners per ``(dims, specs, residual_raw)`` geometry
    (:meth:`PlanCache.set_tile_overrides`); :meth:`PlanCache.plan` applies
    them when building plans, so ``DeployedKAN.replan``, the executors and
    the serving path all pick the tuned geometry up transparently.
    Registering (or clearing) overrides invalidates the matching cached
    plans/compiled entries so no consumer keeps serving the stale geometry.
"""

from __future__ import annotations

import collections
import dataclasses
import threading

__all__ = ["bucket_batch", "PlanKey", "PlanCache", "PLAN_CACHE"]


def bucket_batch(b: int, lo: int = 8) -> int:
    """Round a logical batch up to the next power of two (>= ``lo``)."""
    if b < 1:
        raise ValueError(f"batch must be >= 1, got {b}")
    p = lo
    while p < b:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Hashable identity of one compiled executor variant."""

    dims: tuple
    specs: tuple            # per-layer ASPQuantSpec (frozen dataclasses)
    # padded batch: lo * 2^k (lo=8 unsharded -> powers of two; under a mesh
    # lo=8*data_size, so the bucket divides by ANY data-axis size but is not
    # necessarily a power of two).  GLOBAL (pre-shard) under a mesh.
    bucket: int
    residual_raw: bool
    interpret: bool
    backend: str
    flags: tuple = ()       # backend statics (e.g. ("cim", CIMConfig(...)))
    # mesh fingerprint: () for single-device execution, else (axis names,
    # axis sizes, flat device ids, per-layer model-sharded bools) — see
    # runtime.meshexec.mesh_fingerprint.  Sharded and unsharded entries can
    # therefore never collide, and two meshes only share an entry when they
    # lay the same devices out the same way.
    mesh: tuple = ()


class PlanCache:
    """LRU of PlanKey -> (PipelinePlan, compiled apply) with counters."""

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._plans: collections.OrderedDict = collections.OrderedDict()
        self._tile_overrides: dict = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.traces = 0

    # -- compiled-executor entries --------------------------------------

    def get(self, key: PlanKey, builder):
        """Return the cached (plan, apply) for ``key``; build on miss.

        ``builder(key)`` must return the ``(plan, apply)`` pair; ``apply``
        should bump :attr:`traces` from inside its traced python body so the
        counter reflects actual retraces, not cache misses.
        """
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry
            self.misses += 1
            entry = builder(key)
            self._entries[key] = entry
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
            return entry

    def keys(self) -> list:
        """The PlanKeys of the cached compiled applies, oldest first."""
        with self._lock:
            return list(self._entries)

    def record_trace(self) -> None:
        """Called from inside a jitted apply body: one real (re)trace."""
        self.traces += 1

    # -- plan-only lookups (DeployedKAN.replan) -------------------------

    def plan(self, batch: int, dims: tuple, specs: tuple, *,
             residual_raw: bool = False):
        """Memoized ``make_pipeline_plan`` — replan becomes a dict lookup.

        Applies any tuned tile overrides registered for this geometry, so
        every consumer that resolves plans through the cache transparently
        runs on the tuned block sizes.
        """
        from ..kernels.kan_spline.pipeline import make_pipeline_plan

        key = (batch, tuple(dims), tuple(specs), residual_raw)
        with self._lock:
            plan = self._plans.get(key)
            if plan is None:
                overrides = self._tile_overrides.get(
                    (tuple(dims), tuple(specs), residual_raw)
                )
                plan = make_pipeline_plan(
                    batch, tuple(dims), tuple(specs),
                    residual_raw=residual_raw, tile_overrides=overrides,
                )
                self._plans[key] = plan
                while len(self._plans) > 4 * self.maxsize:
                    self._plans.popitem(last=False)
            else:
                self._plans.move_to_end(key)
            return plan

    # -- tuned tile-plan registry (repro.tune.tiles) --------------------

    def set_tile_overrides(self, dims: tuple, specs: tuple,
                           residual_raw: bool, overrides) -> None:
        """Register (or with ``overrides=None`` clear) a tuned tile plan.

        ``overrides`` is a per-layer ``((bb, bo, bf), ...)`` tuple (see
        ``kernels.kan_spline.pipeline.make_pipeline_plan``).  Cached plans
        and compiled entries for the geometry are invalidated so the next
        resolution rebuilds on the tuned blocks; the tile tuner re-warms the
        hot entry right after registration so consumers keep hitting the
        cache without a retrace of their own.
        """
        from ..kernels.kan_spline.pipeline import normalize_tile_overrides

        gkey = (tuple(dims), tuple(specs), bool(residual_raw))
        with self._lock:
            if overrides is None:
                if gkey not in self._tile_overrides:
                    return  # nothing registered: clearing must not invalidate
                del self._tile_overrides[gkey]
            else:
                self._tile_overrides[gkey] = normalize_tile_overrides(
                    overrides, len(dims) - 1
                )
            for k in [k for k in self._plans
                      if (k[1], k[2], k[3]) == gkey]:
                del self._plans[k]
            for k in [k for k in self._entries
                      if (k.dims, k.specs, k.residual_raw) == gkey]:
                del self._entries[k]

    def get_tile_overrides(self, dims: tuple, specs: tuple,
                           residual_raw: bool):
        """The registered tuned tile plan for a geometry, or None."""
        with self._lock:
            return self._tile_overrides.get(
                (tuple(dims), tuple(specs), bool(residual_raw))
            )

    def tile_overrides(self) -> dict:
        """Snapshot of every registered tuned tile plan (for reporting)."""
        with self._lock:
            return dict(self._tile_overrides)

    # -- stats ----------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "traces": self.traces,
                "entries": len(self._entries),
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._plans.clear()
            self._tile_overrides.clear()
            self.hits = self.misses = self.traces = 0


# The process-wide cache every executor resolves through.
PLAN_CACHE = PlanCache()


def _obs_collect() -> dict:
    """Feed the cache counters to the obs registry under their documented
    dotted names (docs/observability.md) — pulled at snapshot time, so the
    cache's hot path pays nothing for observability."""
    return {f"plan_cache.{k}": v for k, v in PLAN_CACHE.stats().items()}


from ..obs import REGISTRY as _OBS_REGISTRY  # noqa: E402 - avoid cycle risk

_OBS_REGISTRY.register_collector(_obs_collect)
