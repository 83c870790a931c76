"""PlanCache — memoized compilation pipeline for pattern queries.

A cold pattern query pays three plan-time costs the batch CLI used to
re-pay on every invocation: the configuration search (schedules ×
restriction sets × IEP ranked by the perf model), the MatchingPlan
build, and the executor JIT.  The cache pays them once per *isomorphism
class* and replays the warmed matcher afterwards.

Cache key (DESIGN.md §5):
  (canonical pattern key,
   graph-stats fingerprint   — CSR content hash + (|V|, |E|, tri_cnt),
   executor fingerprint      — capacity, dynamic_base, resolved pallas
                               path, bucket layout, sharded?,
   mode, use_iep)
Anything that changes the searched configuration or the compiled
program invalidates the entry by construction; eviction beyond
`max_entries` is LRU, and evicted matchers are `release()`d so their
compiled executables and device arrays actually free HBM in long-lived
serving processes.

With a `PlanStore` attached (query/store.py) the cache becomes
load-through / write-behind: an in-memory miss first consults the
on-disk index — a persisted entry skips the configuration search
entirely and, when an AOT executable is present and accepted, skips
Python re-tracing too (`persist_hits` / `aot_loads` counters); a full
miss writes the searched result back after warmup (`export_fails`
counts AOT serialization failures — the entry still persists plan-only,
and a RuntimeWarning names the cause; an executable the running backend
rejects counts in `aot_load_fails` and warns the same way).
"""
from __future__ import annotations

import warnings
from collections import OrderedDict
from dataclasses import dataclass, replace as dc_replace

from ..core.config_search import (
    Configuration, graphzero_configuration, search_configuration,
)
from ..core.executor import (
    CountResult, ExecutorConfig, Matcher, ShardedMatcher,
)
from ..core.pattern import Pattern
from ..core.perf_model import GraphStats
from ..core.plan import MatchingPlan, build_plan
from ..graph.csr import GraphCSR
from ..obs import get_tracer, timer
from .canon import canonical_form, canonical_key

MODES = ("graphpi", "graphzero", "naive")

# Default LRU bound for serving engines: each entry pins a warmed jitted
# executable (plus stripe arrays when sharded), so an unbounded cache on
# an arbitrary request stream is a memory leak.  Evicted classes just
# pay cold cost again.
DEFAULT_MAX_ENTRIES = 256


def executor_fingerprint(cfg: ExecutorConfig) -> str:
    """The ExecutorConfig facets baked into a jitted count program, as
    the stable string `ExecutorConfig.fingerprint()` — safe to persist
    (the on-disk store digests the whole entry key)."""
    return cfg.fingerprint()


def layout_fingerprint(mesh, axis, chunk: int | None,
                       cfg: ExecutorConfig) -> tuple:
    """Execution-layout part of the cache key: the facets a compiled
    program bakes in beyond ExecutorConfig.  Sharded, that is the mesh
    devices, collective axis, and stripe chunk; single-device it is the
    outer-loop chunk width (the warmed trace's v0 shape).  `chunk` is
    resolved exactly like the matchers resolve it, so chunk=None and an
    explicitly-passed default don't alias into two entries for one
    identical program."""
    if mesh is None:
        return ("single", min(chunk or cfg.capacity, cfg.capacity))
    return (
        "sharded",
        axis if isinstance(axis, str) else tuple(axis),
        int(chunk or max(64, cfg.capacity // 16)),
        tuple((str(k), int(v)) for k, v in mesh.shape.items()),
        tuple(str(d) for d in mesh.devices.flat),
    )


def graph_fingerprint(graph: GraphCSR, stats: GraphStats) -> tuple:
    return (graph.fingerprint, stats.n_vertices, stats.n_edges,
            stats.tri_cnt)


@dataclass
class CacheEntry:
    canon_key: str
    pattern: Pattern            # canonical labeling (name = first requester)
    config: Configuration
    plan: MatchingPlan
    matcher: object             # warmed Matcher | ShardedMatcher
    sharded: bool
    mode: str
    search_seconds: float
    compile_seconds: float
    hits: int = 0
    executions: int = 0         # count() dispatches (coalescing evidence:
                                # N same-class tickets in one round → +1)

    def count(self, *, chunk: int | None = None) -> CountResult:
        """Execute the cached program.  `chunk` stripes the outer vertex
        loop on the single-device path (the sharded matcher fixed its
        stripe layout at build time)."""
        if self.sharded:
            out = self.matcher.count()
        else:
            out = self.matcher.count(chunk=chunk)
        return self._finish(out)

    def count_partial(self, state=None, *, chunk: int | None = None,
                      max_dispatches: int | None = None):
        """Preemptible execution: run up to `max_dispatches` kernel
        dispatches and return ``(state, result)`` — result None while
        work remains (pass state back in to resume; the completed count
        is bit-identical to :meth:`count`).  Sharded programs fix their
        stripe layout in one scanned dispatch, so they ignore the budget
        and always complete (state stays None)."""
        if self.sharded:
            return None, self._finish(self.matcher.count())
        state, out = self.matcher.count_partial(
            state, chunk=chunk, max_dispatches=max_dispatches)
        return state, (None if out is None else self._finish(out))

    def _finish(self, out: CountResult) -> CountResult:
        if self.mode == "naive":
            # no restrictions compiled in: every embedding found |Aut| times
            out = dc_replace(out, count=out.count // self.pattern.aut_count())
        return out


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0              # in-memory misses (incl. persist hits)
    n_searches: int = 0          # configuration searches actually run
    n_compiles: int = 0          # fresh JIT traces (warmup compiles)
    evictions: int = 0
    persist_hits: int = 0        # misses served from the on-disk store
    preloads: int = 0            # entries installed by warm-from-disk
    aot_loads: int = 0           # store loads whose AOT executable loaded
    aot_load_fails: int = 0      # AOT blob rejected -> re-JIT fallback
    export_fails: int = 0        # write-behind AOT export failures
    search_seconds: float = 0.0
    compile_seconds: float = 0.0
    aot_load_seconds: float = 0.0

    def as_dict(self) -> dict:
        return dict(self.__dict__)


class PlanCache:
    """LRU cache of warmed (Configuration, MatchingPlan, Matcher) triples,
    optionally backed by a persistent on-disk `PlanStore`."""

    def __init__(self, *, max_entries: int | None = None, store=None):
        self.max_entries = max_entries
        self.store = store                    # PlanStore | None
        self.stats = CacheStats()
        self._entries: OrderedDict[tuple, CacheEntry] = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def entries(self) -> list[CacheEntry]:
        return list(self._entries.values())

    @staticmethod
    def entry_key(pattern: Pattern, graph_fp: tuple, cfg: ExecutorConfig,
                  *, mode: str = "graphpi", use_iep: bool = False,
                  layout_fp: tuple | None = None) -> tuple:
        if layout_fp is None:
            layout_fp = layout_fingerprint(None, "data", None, cfg)
        # naive ignores use_iep (it always searches without IEP), so the
        # flag must not split one compiled program into two entries
        use_iep = bool(use_iep) and mode != "naive"
        return (canonical_key(pattern), graph_fp,
                executor_fingerprint(cfg), mode, use_iep, layout_fp)

    def get_or_build(
        self,
        pattern: Pattern,
        graph: GraphCSR,
        stats: GraphStats,
        *,
        cfg: ExecutorConfig | None = None,
        mesh=None,
        axis: str = "data",
        mode: str = "graphpi",
        use_iep: bool = False,
        chunk: int | None = None,
        arrays=None,
        warm: bool = True,
        graph_fp: tuple | None = None,
    ) -> tuple[CacheEntry, bool]:
        """Return (entry, was_hit).  Misses run the configuration search,
        build the plan, and (when `warm`) JIT-compile the matcher before
        the entry becomes visible — a hit NEVER searches or compiles.

        `graph_fp` overrides the graph facet of the entry key: live
        engines pass their `EpochStamp.plan_key` (stable across edge
        mutations) so plans and AOT executables survive churn; when
        omitted the legacy content-fingerprint tuple is derived here."""
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; have {MODES}")
        cfg = cfg or ExecutorConfig()
        key = self.entry_key(
            pattern,
            graph_fp if graph_fp is not None
            else graph_fingerprint(graph, stats),
            cfg, mode=mode, use_iep=use_iep,
            layout_fp=layout_fingerprint(mesh, axis, chunk, cfg),
        )
        entry = self._entries.get(key)
        if entry is not None:
            self.stats.hits += 1
            entry.hits += 1
            self._entries.move_to_end(key)
            return entry, True

        self.stats.misses += 1
        # load-through: a persisted entry skips the configuration search
        # (and, when its AOT executable is accepted, the JIT trace too)
        if self.store is not None:
            rec = self.store.load(key)
            if rec is not None:
                self.stats.persist_hits += 1
                entry = self._install_record(
                    rec, key, graph, cfg=cfg, mesh=mesh, axis=axis,
                    chunk=chunk, arrays=arrays, warm=warm)
                self._insert(key, entry)
                return entry, False

        canon = canonical_form(pattern)
        with get_tracer().span("cache.search", canon_key=key[0],
                               mode=mode), timer() as t:
            if mode == "graphpi":
                config = search_configuration(
                    canon, stats, use_iep=use_iep).best
            elif mode == "graphzero":
                config = graphzero_configuration(
                    canon, stats, use_iep=use_iep)
            else:  # naive: no restrictions; entry.count divides by |Aut|
                config = search_configuration(canon, stats,
                                              use_iep=False).best
        search_s = t.seconds
        self.stats.n_searches += 1
        self.stats.search_seconds += search_s

        res_set = () if mode == "naive" else config.res_set
        plan = build_plan(canon, config.order, res_set, iep_k=config.iep_k)
        if mesh is not None:
            matcher = ShardedMatcher(graph, plan, mesh, axis=axis, cfg=cfg,
                                     chunk=chunk, arrays=arrays)
        else:
            matcher = Matcher(graph, plan, cfg, arrays=arrays)
        compile_s = 0.0
        exec_bytes = None
        if warm:
            with get_tracer().span("cache.compile", canon_key=key[0],
                                   mode=mode), timer() as t:
                if mesh is None and self.store is not None:
                    # AOT export BEFORE warmup: export traces/lowers the
                    # program once and install makes warmup compile that
                    # exact lowering — one trace total instead of
                    # trace-compile-retrace, and local serving runs the
                    # same bytes a restarted replica will load
                    try:
                        exec_bytes = matcher.export_bytes(chunk=chunk)
                        matcher.install_exported(exec_bytes, chunk=chunk)
                    except Exception as e:  # noqa: BLE001 — plan-only
                        self.stats.export_fails += 1
                        exec_bytes = None
                        warnings.warn(
                            f"AOT export of {key[0]} failed, the entry "
                            f"persists plan-only: {type(e).__name__}: {e}",
                            RuntimeWarning, stacklevel=2)
                if mesh is not None:
                    matcher.warmup()      # chunk is baked into the stripes
                else:
                    matcher.warmup(chunk=chunk)
            compile_s = t.seconds
            self.stats.n_compiles += 1
            self.stats.compile_seconds += compile_s

        entry = CacheEntry(
            canon_key=key[0], pattern=canon, config=config, plan=plan,
            matcher=matcher, sharded=mesh is not None, mode=mode,
            search_seconds=search_s, compile_seconds=compile_s,
        )
        # write-behind: persist the searched result (+ the AOT executable
        # exported above on the single-device path; sharded programs bake
        # in mesh/device state, so they persist plan-only and re-JIT on
        # restart)
        if self.store is not None:
            self.store.save(
                key, pattern=canon, config=config, plan=plan,
                exec_bytes=exec_bytes, search_seconds=search_s,
                compile_seconds=compile_s)
        self._insert(key, entry)
        return entry, False

    # -------------------------------------------------------- persistence
    def _install_record(self, rec, key: tuple, graph: GraphCSR, *,
                        cfg: ExecutorConfig, mesh, axis: str,
                        chunk: int | None, arrays, warm: bool) -> CacheEntry:
        """Turn a loaded StoreRecord into a live warmed entry — no
        configuration search; no JIT trace either when the record's AOT
        executable installs cleanly (else fall back to a fresh warmup)."""
        if mesh is not None:
            matcher = ShardedMatcher(graph, rec.plan, mesh, axis=axis,
                                     cfg=cfg, chunk=chunk, arrays=arrays)
        else:
            matcher = Matcher(graph, rec.plan, cfg, arrays=arrays)
        compile_s = 0.0
        if warm:
            installed = False
            if rec.exec_bytes is not None and mesh is None:
                try:
                    matcher.install_exported(rec.exec_bytes, chunk=chunk)
                    installed = True
                except ValueError as e:     # wrong platform or shapes
                    self.stats.aot_load_fails += 1
                    warnings.warn(
                        f"AOT executable of {key[0]} rejected, re-JIT: {e}",
                        RuntimeWarning, stacklevel=2)
            with get_tracer().span("cache.warm", canon_key=key[0],
                                   aot=installed), timer() as t:
                if mesh is not None:
                    matcher.warmup()
                else:
                    matcher.warmup(chunk=chunk)
            dt = t.seconds
            if installed:
                self.stats.aot_loads += 1
                self.stats.aot_load_seconds += dt
            else:
                compile_s = dt
                self.stats.n_compiles += 1
                self.stats.compile_seconds += dt
        return CacheEntry(
            canon_key=key[0], pattern=rec.pattern, config=rec.config,
            plan=rec.plan, matcher=matcher, sharded=mesh is not None,
            mode=rec.mode, search_seconds=0.0, compile_seconds=compile_s,
        )

    def preload(self, graph: GraphCSR, stats: GraphStats, *,
                cfg: ExecutorConfig | None = None, mesh=None,
                axis: str = "data", chunk: int | None = None,
                arrays=None, warm: bool = True,
                graph_fp: tuple | None = None) -> int:
        """Warm-from-disk: install every store record compatible with the
        current serving context (same graph/executor/layout fingerprints
        — checked by re-deriving each record's key digest) before the
        first request arrives.  Returns the number of entries installed.
        `graph_fp` as in :meth:`get_or_build` (live epoch plan keys)."""
        if self.store is None:
            return 0
        from .store import key_digest

        cfg = cfg or ExecutorConfig()
        gfp = (graph_fp if graph_fp is not None
               else graph_fingerprint(graph, stats))
        lfp = layout_fingerprint(mesh, axis, chunk, cfg)
        installed = 0
        for rec in self.store.records():
            key = self.entry_key(rec.pattern, gfp, cfg, mode=rec.mode,
                                 use_iep=rec.use_iep, layout_fp=lfp)
            if key_digest(key) != rec.digest or key in self._entries:
                continue
            self.stats.preloads += 1
            self._insert(key, self._install_record(
                rec, key, graph, cfg=cfg, mesh=mesh, axis=axis,
                chunk=chunk, arrays=arrays, warm=warm))
            installed += 1
        return installed

    # ------------------------------------------------------------ eviction
    def _insert(self, key: tuple, entry: CacheEntry) -> None:
        self._entries[key] = entry
        if self.max_entries is not None:
            while len(self._entries) > self.max_entries:
                _, evicted = self._entries.popitem(last=False)
                # explicitly drop the warmed matcher's compiled
                # executables + device-array references: entries in a
                # long-lived serving process must release HBM on
                # eviction, not whenever GC gets around to the cycle.
                # (max_entries=0 pops `entry` itself — the caller is
                # about to count on it, so it must stay live.)
                if evicted is not entry:
                    evicted.matcher.release()
                self.stats.evictions += 1
