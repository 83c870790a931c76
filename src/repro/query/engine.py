"""QueryEngine — the batched pattern-count request path.

Loads a dataset ONCE: the CSR is uploaded to device memory a single
time (shared by every cached matcher via ``arrays=``), graph statistics
are computed once at startup, and when the process has multiple JAX
devices the graph stays resident on the mesh with the executor's
fine-grained outer-loop striping (`ShardedMatcher`).  Requests then
stream through the `PlanCache`: the first query of an isomorphism
class pays configuration search + JIT, repeats replay the warmed
program.

Request surface (DESIGN.md §5).  The engine is asynchronous-by-default
so the serving Gateway can schedule it against other mesh tenants:

  * ``plan(request)``    — cache/plan resolution only (search + JIT on
                           a miss); never executes a count.
  * ``enqueue(request)`` — admit a request, returning a :class:`Ticket`
                           that resolves later (raises
                           :class:`AdmissionRejected` past the
                           per-tenant depth bound; ``try_enqueue``
                           returns the :class:`Rejection` instead).
  * ``run_pending(limit)`` — execute up to ``limit`` queued tickets as
                           one round, COALESCING tickets of the same
                           isomorphism class (× mode × use_iep) into a
                           single plan execution: N bursty duplicates
                           cost one kernel dispatch, and the N−1
                           riders are accounted as cache hits.

MULTI-TENANCY.  Every request carries a ``tenant`` id; queued tickets
live in per-tenant FIFO queues drained by deterministic weighted
round-robin (``tenant_shares``), each tenant's depth bounded by
``tenant_depth`` (admission control: reject-with-reason, counted).
PREEMPTION.  With ``preempt_dispatches=k`` a round issues at most `k`
kernel dispatches: a class whose chunked outer loop is mid-flight
checkpoints its span stack (`CountState`) and resumes NEXT round —
rotated behind any other waiting class, so one huge query cannot
monopolize the device.  A preempted-and-resumed count is bit-identical
to an uninterrupted one (the state is the exact work stack + raw
totals).

``submit()``/``serve()`` remain as deprecated synchronous shims (one
request per round — the exact pre-Gateway behaviour).  Per-query wall
latency is recorded; `summary()` reports p50/p99 plus the cache
counters that prove hits never re-search or re-compile;
``tenant_report()`` adds per-tenant p50/p99 and admission counters.
"""
from __future__ import annotations

import warnings
from collections import deque
from dataclasses import dataclass

import numpy as np

from ..core.executor import (ExecutorConfig, Matcher, ShardedMatcher,
                             compute_stats, device_graph)
from ..core.pattern import Pattern
from ..core.perf_model import GraphStats
from ..graph.csr import GraphCSR
from ..live import (CompactionPolicy, CountMaintainer, DeltaOverlay,
                    EpochStamp, maybe_compact, stats_drifted)
from ..obs import MetricsRegistry, get_tracer, latency_summary, timer
from .cache import DEFAULT_MAX_ENTRIES, CacheEntry, PlanCache
from .canon import canonical_key


DEFAULT_TENANT = "default"


@dataclass(frozen=True)
class QueryRequest:
    """One pattern-count request (per-request options ride along)."""

    pattern: Pattern
    use_iep: bool = False
    verify: bool = False          # check against the pure-python oracle
    mode: str = "graphpi"
    tenant: str = DEFAULT_TENANT  # multi-tenant queue / fairness id


@dataclass
class QueryResult:
    pattern_name: str
    canon_key: str
    count: int
    latency_s: float              # wall time incl. cache miss costs
    cache_hit: bool
    mode: str
    use_iep: bool
    order: tuple
    res_set: tuple
    iep_k: int
    search_seconds: float         # 0.0 on a hit
    compile_seconds: float        # 0.0 on a hit
    overflowed: bool
    max_needed: int
    expected: int | None = None   # oracle count when verified
    verified: bool | None = None  # None = not requested
    coalesced: bool = False       # resolved by another ticket's execution

    def line(self) -> str:
        """One human-readable serving-log line."""
        v = ("" if self.verified is None
             else ("  verify=OK" if self.verified else "  verify=MISMATCH"))
        o = "  OVERFLOWED" if self.overflowed else ""
        how = "HIT " if self.cache_hit else "MISS"
        if self.coalesced:
            how = "COAL"
        return (f"{self.pattern_name:<16} count={self.count:<12} "
                f"{how} "
                f"lat={self.latency_s * 1e3:8.1f}ms "
                f"(search={self.search_seconds:.3f}s "
                f"compile={self.compile_seconds:.3f}s){v}{o}")


@dataclass(frozen=True)
class PlannedQuery:
    """What ``plan()`` resolves: the warmed cache entry plus whether the
    resolution was a cache hit (misses paid search/JIT just now)."""

    entry: CacheEntry
    cache_hit: bool


@dataclass(frozen=True)
class Rejection:
    """Why admission control refused a request (deterministic, counted)."""

    tenant: str
    reason: str
    depth: int                    # tenant's queue depth at rejection time
    limit: int                    # the configured bound it hit


class AdmissionRejected(RuntimeError):
    """Raised by :meth:`QueryEngine.enqueue` when a tenant's queue is at
    its depth bound; carries the structured :class:`Rejection`."""

    def __init__(self, rejection: Rejection):
        super().__init__(
            f"tenant {rejection.tenant!r} rejected: {rejection.reason} "
            f"(depth={rejection.depth}, limit={rejection.limit})")
        self.rejection = rejection


@dataclass
class Ticket:
    """Handle for an enqueued request; resolves when a round executes it
    (``QueryEngine.run_pending`` or the Gateway's graph workload)."""

    request: QueryRequest
    seq: int
    _result: QueryResult | None = None
    cancelled: bool = False

    @property
    def done(self) -> bool:
        return self._result is not None

    @property
    def result(self) -> QueryResult:
        if self._result is None:
            raise RuntimeError(
                f"ticket #{self.seq} not resolved yet — run the engine's "
                f"pending queue (run_pending) or schedule it via the Gateway")
        return self._result


@dataclass
class _InFlight:
    """One isomorphism-class group mid-round: its tickets, the resolved
    plan (lazy), and the resumable count checkpoint (`CountState`) when a
    preemption budget suspended it between kernel dispatches."""

    key: tuple
    tickets: list
    planned: PlannedQuery | None = None
    state: object | None = None   # core.executor.CountState when started
    seconds: float = 0.0          # accumulated plan + execute wall time


class QueryEngine:
    """Serve pattern-count queries over one resident graph.

    Parameters
    ----------
    graph:   the data graph, loaded once.
    cfg:     executor configuration shared by every cached matcher
             (part of the cache key).
    mesh:    optional JAX mesh; when given, counting runs sharded over
             `axis` with the CSR resident mesh-wide.
    chunk:   vertex-chunk striping of the outer loop — smaller chunks
             bound frontier memory and give the overflow bisection finer
             grain at the price of more kernel dispatches per query
             (latency/footprint trade-off, DESIGN.md §5).
    tenant_depth:  admission bound — max queued (unresolved, uncancelled)
             tickets per tenant; ``None`` (default) admits everything.
    tenant_shares: tickets drained per tenant per take-cycle of the
             weighted round-robin (missing tenants weigh 1).
    preempt_dispatches: default per-round kernel-dispatch budget; a class
             still mid-count when the budget runs out is checkpointed and
             rotated behind other waiting classes.  ``None`` = run every
             class in the round to completion (pre-preemption behaviour).
    live:    ``True`` (or a prebuilt `DeltaOverlay`) serves over a
             MUTABLE graph: `request_mutation` queues insert/delete/
             compact verbs that apply atomically at round boundaries
             (src/repro/live/) — plans/AOT survive mutations via the
             stats-epoch plan key, counts memoize/invalidate on the
             edge-epoch key, and a `CountMaintainer` refreshes only
             dirty root spans.
    compaction_policy: live-mode thresholds (`live.CompactionPolicy`).
    """

    def __init__(self, graph: GraphCSR, *, cfg: ExecutorConfig | None = None,
                 mesh=None, axis: str = "data", chunk: int | None = None,
                 cache: PlanCache | None = None,
                 store=None,
                 stats: GraphStats | None = None,
                 metrics: MetricsRegistry | None = None,
                 tenant_depth: int | None = None,
                 tenant_shares: dict[str, int] | None = None,
                 preempt_dispatches: int | None = None,
                 live=None,
                 compaction_policy: CompactionPolicy | None = None):
        if live is True:
            live = DeltaOverlay(graph)
        elif live is not None and not isinstance(live, DeltaOverlay):
            raise TypeError(
                f"live must be True or a DeltaOverlay, got {type(live)!r}")
        self.live = live
        if live is not None:
            graph = live.view              # executor-facing adjacency
        self.graph = graph
        self.cfg = cfg or ExecutorConfig()
        self.mesh = mesh
        self.axis = axis
        self.chunk = chunk
        if cache is None:
            cache = PlanCache(max_entries=DEFAULT_MAX_ENTRIES, store=store)
        elif store is not None and cache.store is None:
            cache.store = store             # attach persistence to the
        self.cache = cache                  # caller-provided cache
        self._arrays = device_graph(graph)     # ONE resident CSR upload
        with timer() as t:
            if stats is None:
                # a restarted engine skips the startup triangle count when
                # the attached store has a stats record for this exact graph
                # (content fingerprint); compute-and-persist otherwise
                if self.cache.store is not None:
                    stats = self.cache.store.load_graph_stats(
                        graph.fingerprint)
                if stats is None:
                    stats = compute_stats(graph, self.cfg)
                    if self.cache.store is not None:
                        self.cache.store.save_graph_stats(
                            graph.fingerprint, stats)
        self.stats = stats
        self.stats_seconds = t.seconds
        # round-boundary epoch identity: serving code carries THIS
        # stamp, never raw fingerprints (`no-stale-fingerprint`)
        self._epoch = (EpochStamp.for_live(live, stats) if live is not None
                       else EpochStamp.legacy(graph, stats))
        self._maintainer = (CountMaintainer(live) if live is not None
                            else None)
        self.compaction_policy = compaction_policy or CompactionPolicy()
        self._mutations: deque = deque()       # queued (verb, edges) batches
        self.mutations_applied = 0             # effective edge changes
        self.last_round_mutations = 0          # batches applied last round
        self.matcher_rebinds = 0               # zero-recompile epoch swaps
        self.matcher_rebuilds = 0              # shape-growth rebuilds
        # registries are per-engine (benchmarks/run.py executes several
        # benchmark mains in one process; each needs a clean window) —
        # launchers that want one pane pass a shared instance
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._lat_hist = self.metrics.histogram("engine.query_latency_ms")
        self.metrics.register_collector(self._collect)
        self._edges = None                     # lazy, for oracle verification
        self._oracle: dict[str, int] = {}      # canon_key -> oracle count
        self._queues: dict[str, deque] = {}    # tenant -> FIFO of Tickets
        self._inflight: deque = deque()        # _InFlight groups, mid-round
        self._seq = 0
        self.tenant_depth = tenant_depth
        self.tenant_shares = dict(tenant_shares or {})
        self.preempt_dispatches = preempt_dispatches
        # round-execution counters (the coalescing/preemption evidence)
        self.requests_resolved = 0
        self.executions = 0                    # completed class executions
        self.coalesced = 0                     # tickets riding an execution
        self.preemptions = 0                   # groups suspended mid-count
        self.last_round_dispatches = 0         # kernel dispatches last round
        self.rejections: dict[str, int] = {}   # tenant -> admission rejects
        self._resolved_by_tenant: dict[str, int] = {}

    def _collect(self) -> dict:
        """Engine/cache/store counters for `metrics.snapshot()` — the
        dataclass stats objects stay the storage; this merges them into
        the one `subsystem.metric` pane."""
        out = {
            "engine.requests_resolved": self.requests_resolved,
            "engine.executions": self.executions,
            "engine.coalesced": self.coalesced,
            "engine.pending": self.pending(),
            "engine.inflight": self.inflight(),
            "engine.preemptions": self.preemptions,
            "engine.admission_rejected": sum(self.rejections.values()),
            "engine.cache_entries": len(self.cache),
        }
        for k, v in self.cache.stats.as_dict().items():
            out[f"cache.{k}"] = v
        if self.cache.store is not None:
            for k, v in self.cache.store.stats.as_dict().items():
                out[f"store.{k}"] = v
        if self.live is not None:
            out.update({
                "live.epoch": self.live.edge_epoch,
                "live.stats_epoch": self.live.stats_epoch,
                "live.overlay_edges": self.live.overlay_edges(),
                "live.compactions": self.live.compactions,
                "live.mutations_applied": self.mutations_applied,
                "live.pending_mutations": len(self._mutations),
                "live.matcher_rebinds": self.matcher_rebinds,
                "live.matcher_rebuilds": self.matcher_rebuilds,
            })
            for k, v in self._maintainer.counters().items():
                out[f"live.{k}"] = v
        return out

    # ------------------------------------------------------ async serving
    def plan(self, request: QueryRequest) -> PlannedQuery:
        """Cache/plan resolution ONLY — search + plan build + JIT warmup
        on a miss, pure lookup on a hit.  Never executes a count."""
        with get_tracer().span(
                "engine.plan", pattern=request.pattern.name or "anon",
                mode=request.mode) as sp:
            entry, hit = self.cache.get_or_build(
                request.pattern, self.graph, self.stats,
                cfg=self.cfg, mesh=self.mesh, axis=self.axis,
                mode=request.mode, use_iep=request.use_iep,
                chunk=self.chunk, arrays=self._arrays,
                graph_fp=self._epoch.plan_key,
            )
            sp.set(cache_hit=hit, canon_key=entry.canon_key)
        return PlannedQuery(entry=entry, cache_hit=hit)

    def try_enqueue(self, request: QueryRequest) -> Ticket | Rejection:
        """Admission-controlled enqueue: returns a :class:`Ticket`, or a
        :class:`Rejection` when the request's tenant already has
        ``tenant_depth`` tickets queued.  Rejections are deterministic
        (a pure function of the queue depth at call time) and counted
        per tenant (``rejections`` / ``engine.admission_rejected``)."""
        tenant = request.tenant
        q = self._queues.setdefault(tenant, deque())
        if self.tenant_depth is not None and len(q) >= self.tenant_depth:
            self.rejections[tenant] = self.rejections.get(tenant, 0) + 1
            self.metrics.counter("engine.admission_rejected",
                                 tenant=tenant).inc()
            return Rejection(tenant=tenant, reason="queue depth bound",
                             depth=len(q), limit=self.tenant_depth)
        ticket = Ticket(request=request, seq=self._seq)
        self._seq += 1
        q.append(ticket)
        return ticket

    def enqueue(self, request: QueryRequest) -> Ticket:
        """Admit a request; the returned ticket resolves when a round
        executes it (:meth:`run_pending`, or the Gateway's scheduler).
        Raises :class:`AdmissionRejected` past the tenant depth bound."""
        out = self.try_enqueue(request)
        if isinstance(out, Rejection):
            raise AdmissionRejected(out)
        return out

    def cancel(self, ticket: Ticket) -> bool:
        """Withdraw a still-queued ticket (marks it ``cancelled`` and
        removes it from its tenant queue).  Returns False when the ticket
        already resolved, was cancelled before, or is mid-execution in an
        in-flight group (a dispatched count is not torn down)."""
        if ticket.done or ticket.cancelled:
            return False
        q = self._queues.get(ticket.request.tenant)
        if q is None or ticket not in q:
            return False
        q.remove(ticket)
        ticket.cancelled = True
        return True

    def pending(self, tenant: str | None = None) -> int:
        """Queued (not yet taken into a round) ticket count — one tenant
        or all."""
        if tenant is not None:
            return len(self._queues.get(tenant, ()))
        return sum(len(q) for q in self._queues.values())

    def inflight(self) -> int:
        """Tickets taken into a round whose class is still mid-count
        (checkpointed by the preemption budget, resumes next round)."""
        return sum(len(f.tickets) for f in self._inflight)

    # --------------------------------------------------------- mutation
    def mutations_pending(self) -> int:
        """Queued mutation batches (0 for non-live engines — safe for
        schedulers to poll unconditionally)."""
        return len(self._mutations)

    def request_mutation(self, verb: str, edges=None) -> dict:
        """Queue one mutation batch (`insert_edges` / `delete_edges` /
        `compact`).  Batches apply atomically at the START of the next
        round — never under an in-flight `CountState` — so a query
        submitted after this call is answered on the post-mutation
        epoch.  Returns an ack with the queue depth and current epoch."""
        if self.live is None:
            raise RuntimeError(
                "engine is not live: construct QueryEngine(..., live=True) "
                "to serve mutate verbs")
        from ..live import MUTATION_VERBS

        if verb not in MUTATION_VERBS:
            raise ValueError(
                f"unknown mutation verb {verb!r}; have {MUTATION_VERBS}")
        batch = None
        if verb != "compact":
            batch = [(int(e[0]), int(e[1])) for e in (edges or ())]
        self._mutations.append((verb, batch))
        return {
            "verb": verb,
            "queued_edges": 0 if batch is None else len(batch),
            "pending_batches": len(self._mutations),
            "edge_epoch": self.live.edge_epoch,
        }

    def _apply_mutations(self) -> int:
        """Drain the mutation queue at a round boundary.

        In-flight groups are cleanly RE-ENQUEUED (their tickets return
        to the head of their tenant queues in admission order and the
        partial `CountState`s are dropped): a preempted count never
        resumes across an epoch, so every resolved count is computed
        entirely on one epoch's adjacency."""
        live = self.live
        batches = len(self._mutations)
        requeue = [t for fl in self._inflight for t in fl.tickets]
        with get_tracer().span("engine.mutate", batches=batches,
                               requeued=len(requeue)):
            self._inflight.clear()
            for t in sorted(requeue, key=lambda t: t.seq, reverse=True):
                self._queues.setdefault(t.request.tenant,
                                        deque()).appendleft(t)
            applied = 0
            while self._mutations:
                verb, batch = self._mutations.popleft()
                applied += live.apply(verb, batch)
            maybe_compact(live, self.compaction_policy)
            if stats_drifted(live, self.stats, self.compaction_policy):
                # |E| moved materially: plans stay valid but their
                # perf-model ranking is stale — bump the stats epoch so
                # the next plan() re-searches under fresh statistics
                live.stats_epoch += 1
                self.stats = compute_stats(live.view, self.cfg)
                if self.cache.store is not None:
                    self.cache.store.save_graph_stats(
                        live.view.fingerprint, self.stats)
            self._refresh_live()
        self.mutations_applied += applied
        self.last_round_mutations = batches
        if self.cache.store is not None:
            self.cache.store.save_overlay(live.to_record())
        return applied

    def _refresh_live(self) -> None:
        """Swap the new epoch's view/device arrays into the engine and
        every cached matcher.  Fixed overlay shapes make this a rebind
        (zero recompiles); genuine growth rebuilds matchers honestly
        (counted in `cache.stats.n_compiles` / `matcher_rebuilds`)."""
        live = self.live
        view = live.view
        arrays = device_graph(view)
        for entry in self.cache.entries():
            try:
                entry.matcher.rebind(arrays, graph=view)
                self.matcher_rebinds += 1
            except ValueError:
                if entry.sharded:
                    matcher = ShardedMatcher(
                        view, entry.plan, self.mesh, axis=self.axis,
                        cfg=self.cfg, chunk=self.chunk, arrays=arrays)
                    matcher.warmup()
                else:
                    matcher = Matcher(view, entry.plan, self.cfg,
                                      arrays=arrays)
                    matcher.warmup(chunk=self.chunk)
                self.cache.stats.n_compiles += 1
                entry.matcher.release()
                entry.matcher = matcher
                self.matcher_rebuilds += 1
        self.graph = view
        self._arrays = arrays
        self._epoch = EpochStamp.for_live(live, self.stats)
        # oracle memos are content-addressed to the old epoch
        self._oracle.clear()
        self._edges = None

    @staticmethod
    def _group_key(request: QueryRequest) -> tuple:
        # mirrors PlanCache.entry_key normalization: naive ignores
        # use_iep, so the flag must not split one round group either
        use_iep = bool(request.use_iep) and request.mode != "naive"
        return (canonical_key(request.pattern), request.mode, use_iep)

    def _take_tickets(self, limit: int | None) -> list[Ticket]:
        """Drain up to ``limit`` tickets across tenant queues by
        deterministic weighted round-robin: tenants are visited in
        first-seen order, each yielding up to ``tenant_shares[tenant]``
        (default 1) tickets per cycle, until the limit or every queue is
        empty.  A single tenant degenerates to exact FIFO."""
        out: list[Ticket] = []
        while limit is None or len(out) < limit:
            progressed = False
            for tenant, q in self._queues.items():
                share = max(int(self.tenant_shares.get(tenant, 1)), 1)
                for _ in range(share):
                    if not q or (limit is not None and len(out) >= limit):
                        break
                    out.append(q.popleft())
                    progressed = True
            if not progressed:
                break
        return out

    def run_pending(self, limit: int | None = None, *,
                    max_dispatches: int | None = None) -> list[Ticket]:
        """Execute up to ``limit`` queued tickets as ONE round.

        Tickets whose requests fall in the same isomorphism class (and
        mode/use_iep) are coalesced: the class is planned and executed
        once, and every rider ticket resolves with that count — riders
        are accounted as cache hits (they never search, compile, or
        dispatch).  Distinct classes in the round are micro-batched
        back-to-back against the warmed resident graph.

        With a dispatch budget (``max_dispatches`` here, or the engine's
        ``preempt_dispatches`` default) the round is PREEMPTIVE: once the
        budget is spent, the mid-count class checkpoints its chunk stack
        and rotates to the back of the in-flight queue; the next round
        resumes it after any other waiting classes.  Tickets of a
        suspended class resolve in the round that completes it.

        Returns the tickets resolved THIS round, in admission order.
        """
        if limit is not None and limit < 0:
            # a negative slice would silently drop the newest tickets
            raise ValueError(f"limit must be >= 0, got {limit}")
        budget_n = (self.preempt_dispatches if max_dispatches is None
                    else max_dispatches)
        remaining = None if budget_n is None else max(int(budget_n), 1)
        self.last_round_dispatches = 0
        self.last_round_mutations = 0
        if self._mutations:
            # round boundary: apply queued mutations BEFORE taking
            # tickets, so everything executed below runs on one epoch
            self._apply_mutations()
        take = self._take_tickets(limit)
        fresh = 0
        for t in take:
            key = self._group_key(t.request)
            fl = next((f for f in self._inflight if f.key == key), None)
            if fl is not None:
                # same class already mid-round: ride its execution
                fl.tickets.append(t)
            else:
                self._inflight.append(_InFlight(key=key, tickets=[t]))
                fresh += 1
        if not self._inflight:
            return []
        resolved: list[Ticket] = []
        with get_tracer().span("engine.round", tickets=len(take),
                               groups=fresh,
                               coalesced=len(take) - fresh,
                               budget=-1 if remaining is None else remaining):
            while self._inflight:
                if remaining is not None and remaining <= 0:
                    break
                fl = self._inflight.popleft()
                done, used = self._run_group(fl, remaining)
                self.last_round_dispatches += used
                if remaining is not None:
                    remaining -= used
                if done:
                    resolved.extend(fl.tickets)
                else:
                    # suspended mid-count: rotate BEHIND other waiting
                    # classes so they complete between this one's quanta
                    self.preemptions += 1
                    self._inflight.append(fl)
        resolved.sort(key=lambda t: t.seq)
        return resolved

    def _run_group(self, fl: _InFlight,
                   remaining: int | None) -> tuple[bool, int]:
        """Start or resume one class group under a dispatch budget.
        Returns (completed, dispatches_used); on completion every ticket
        in the group is resolved with the (bit-identical) final count."""
        lead = fl.tickets[0].request
        if fl.planned is None:
            with timer() as t_plan:
                fl.planned = self.plan(lead)
            fl.seconds += t_plan.seconds
        entry, hit = fl.planned.entry, fl.planned.cache_hit
        before = 0 if fl.state is None else fl.state.dispatches
        with get_tracer().span(
                "engine.execute", pattern=lead.pattern.name or "anon",
                canon_key=entry.canon_key, cache_hit=hit,
                riders=len(fl.tickets) - 1,
                tickets=[t.seq for t in fl.tickets],
                resumed=fl.state is not None):
            with timer() as t_run:
                if self._maintainer is not None:
                    fl.state, out = self._maintainer.count_partial(
                        fl.key, entry, fl.state, chunk=self.chunk,
                        max_dispatches=remaining)
                else:
                    fl.state, out = entry.count_partial(
                        fl.state, chunk=self.chunk, max_dispatches=remaining)
            fl.seconds += t_run.seconds
        # sharded counts report no per-dispatch state (one logical unit)
        used = (1 if fl.state is None
                else max(fl.state.dispatches - before, 0))
        if out is None:
            return False, used
        entry.executions += 1
        self.executions += 1
        latency = fl.seconds

        expected = None
        if any(t.request.verify for t in fl.tickets):
            with get_tracer().span("engine.verify",
                                   canon_key=entry.canon_key):
                expected = self._oracle_count(entry.canon_key,
                                              lead.pattern)
        for j, t in enumerate(fl.tickets):
            self._lat_hist.observe(latency * 1e3)
            self.metrics.histogram("engine.query_latency_ms",
                                   tenant=t.request.tenant).observe(
                                       latency * 1e3)
            self.requests_resolved += 1
            self._resolved_by_tenant[t.request.tenant] = (
                self._resolved_by_tenant.get(t.request.tenant, 0) + 1)
            if j > 0:
                # a coalesced rider is a logical cache hit: it was served
                # without a search, a compile, or its own dispatch
                self.cache.stats.hits += 1
                entry.hits += 1
                self.coalesced += 1
            verified = (expected == out.count
                        if t.request.verify and expected is not None else None)
            t._result = QueryResult(
                pattern_name=t.request.pattern.name or "anon",
                canon_key=entry.canon_key,
                count=out.count,
                latency_s=latency,
                cache_hit=hit if j == 0 else True,
                mode=t.request.mode,
                use_iep=t.request.use_iep,
                order=entry.config.order,
                res_set=entry.plan.res_set,
                iep_k=entry.config.iep_k,
                search_seconds=0.0 if (hit or j > 0) else entry.search_seconds,
                compile_seconds=0.0 if (hit or j > 0)
                else entry.compile_seconds,
                overflowed=out.overflowed,
                max_needed=out.max_needed,
                expected=expected if t.request.verify else None,
                verified=verified,
                coalesced=j > 0,
            )
        return True, used

    def _oracle_count(self, canon_key: str, pattern: Pattern) -> int:
        # oracle counts are (label-)isomorphism-invariant — memoize per
        # class; the canonical key already separates label variants
        if canon_key not in self._oracle:
            from ..core.oracle import count_embeddings_oracle

            if self._edges is None:
                self._edges = self.graph.edge_array()
            self._oracle[canon_key] = count_embeddings_oracle(
                self.graph.n, self._edges, pattern,
                labels=self.graph.labels)
        return self._oracle[canon_key]

    # ------------------------------------------- deprecated sync serving
    def submit(self, request: QueryRequest) -> QueryResult:
        """Deprecated synchronous path: one request, one round.

        Thin wrapper over :meth:`enqueue` + :meth:`run_pending(limit=1)`
        — bit-identical counts and identical cache accounting to the
        pre-Gateway implementation (no coalescing at round size 1)."""
        warnings.warn(
            "QueryEngine.submit() is deprecated; use plan()/enqueue() with "
            "run_pending(), or schedule the engine through "
            "repro.serve.gateway.Gateway",
            DeprecationWarning, stacklevel=2)
        ticket = self.enqueue(request)
        # the queue is FIFO: earlier enqueue()d tickets (if any) resolve
        # first, one per round, until ours does
        while not ticket.done and (self.pending() or self.inflight()):
            self.run_pending(limit=1)
        return ticket.result

    def serve(self, requests) -> list[QueryResult]:
        """Deprecated synchronous path: each request is its own round
        (sequential, no coalescing — the pre-Gateway behaviour)."""
        warnings.warn(
            "QueryEngine.serve() is deprecated; enqueue() tickets and "
            "schedule them via repro.serve.gateway.Gateway",
            DeprecationWarning, stacklevel=2)
        out = []
        for r in requests:
            ticket = self.enqueue(r)
            while not ticket.done and (self.pending() or self.inflight()):
                self.run_pending(limit=1)
            out.append(ticket.result)
        return out

    def warm_from_disk(self) -> int:
        """Preload every persisted plan compatible with this engine's
        (graph, executor, layout) before the first request arrives, so a
        restarted replica serves warm from query one.  Returns the
        number of entries installed (0 without an attached store)."""
        return self.cache.preload(
            self.graph, self.stats, cfg=self.cfg, mesh=self.mesh,
            axis=self.axis, chunk=self.chunk, arrays=self._arrays,
            graph_fp=self._epoch.plan_key)

    # ------------------------------------------------------------- reporting
    def reset_window(self) -> None:
        """Start a fresh measurement window (e.g. between benchmark
        warmup and measured phases): registry histograms and counters
        zero; cache/store state and the dataclass counters (which
        describe the whole process lifetime) are untouched.  The Gateway
        exposes the same method on its registry, so both sides of a
        serving benchmark reset identically."""
        self.metrics.reset_window()

    def reset_latencies(self) -> None:
        """Deprecated spelling of :meth:`reset_window` (kept for the
        benchmark harness)."""
        self.reset_window()

    def latency_percentiles(self, tenant: str | None = None) -> dict:
        """Per-query wall-latency summary from the registry histogram
        (`engine.query_latency_ms`, optionally the per-tenant labelled
        series) — same keys as the Gateway's per-turn summaries:
        n / p50_ms / p95_ms / p99_ms / mean_ms."""
        if tenant is None:
            return latency_summary(self._lat_hist)
        return latency_summary(
            self.metrics.histogram("engine.query_latency_ms", tenant=tenant))

    def tenant_report(self) -> dict:
        """Per-tenant serving report: resolved / rejected / queued depths
        plus the tenant's own latency percentiles (the gateway report and
        `benchmarks/gateway_mix.py` read p99 from here)."""
        tenants = sorted(set(self._queues)
                         | set(self._resolved_by_tenant)
                         | set(self.rejections))
        out = {}
        for t in tenants:
            out[t] = {
                "resolved": self._resolved_by_tenant.get(t, 0),
                "rejected": self.rejections.get(t, 0),
                "pending": self.pending(t),
                "share": max(int(self.tenant_shares.get(t, 1)), 1),
                "latency": self.latency_percentiles(t),
            }
        return out

    def summary(self) -> dict:
        out = {
            "graph": self.graph.name,
            "executor": self.cfg.fingerprint(),
            "devices": 1 if self.mesh is None else int(
                np.prod(list(self.mesh.shape.values()))),
            "stats_seconds": self.stats_seconds,
            "latency": self.latency_percentiles(),
            "cache": self.cache.stats.as_dict(),
            "cache_entries": len(self.cache),
            "requests_resolved": self.requests_resolved,
            "executions": self.executions,
            "coalesced": self.coalesced,
            "preemptions": self.preemptions,
            "rejections": sum(self.rejections.values()),
            "tenants": self.tenant_report(),
        }
        if self.cache.store is not None:
            out["store"] = self.cache.store.stats.as_dict()
        if self.live is not None:
            out["live"] = {
                "edge_epoch": self.live.edge_epoch,
                "stats_epoch": self.live.stats_epoch,
                "overlay_edges": self.live.overlay_edges(),
                "compactions": self.live.compactions,
                "mutations_applied": self.mutations_applied,
                "matcher_rebinds": self.matcher_rebinds,
                "matcher_rebuilds": self.matcher_rebuilds,
                **self._maintainer.counters(),
            }
        return out
