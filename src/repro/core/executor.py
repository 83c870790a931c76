"""JAX vectorized pattern-matching executor.

TPU-native adaptation of GraphPi's nested-loop DFS (DESIGN.md §3):

 * level-synchronous frontier expansion — a dense [capacity, depth] matrix
   of partial embeddings is expanded one schedule position at a time;
 * candidate generation gathers a fixed-width window from the flat CSR
   `indices` array at the (dynamically chosen) minimum-degree predecessor;
 * ONE shared per-level admissibility core (`expand_core`) serves every
   path — bucketed and single-window expansion, the last-level popcount
   and the IEP-tail cardinalities.  On the Pallas path the whole level
   (membership against all predecessors + restriction + injectivity
   masks, reduced to a mask or an in-kernel popcount) is a single fused
   kernel pass over the candidate matrix; the portable path is a
   vectorized binary search over flat CSR segments plus XLA masks;
 * compaction is a cumsum scatter (stream compaction);
 * labeled plans prune candidates BEFORE membership: the window is
   gathered from the base predecessor's per-label CSR segment
   (graph.label_view), so only same-label candidates ever reach the
   membership intersection — identically on the portable and fused
   paths, which share the gather and differ only in membership;
 * the IEP tail is evaluated in closed form per surviving prefix;
 * distribution = `shard_map` over the mesh `data` axis with the paper's
   fine-grained outer-loop task striping (device d owns tasks d, d+P, ...).

Counts are exact int64 (x64 enabled locally inside the public entry
points; everything else in the framework pins its own dtypes).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import GraphCSR
from ..obs import get_tracer, set_annotator
from .pattern import Pattern, clique
from .perf_model import GraphStats
from .plan import MatchingPlan, build_plan
from .restrictions import generate_restriction_sets


def _profiler_annotation(name: str):
    """A host event of the profiler's own trace for a program span, while
    a profiler capture records: program spans and device ops then share
    one clock in the capture."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name)
    return None


set_annotator(_profiler_annotation)


# --------------------------------------------------------------------------
# low-level primitives
# --------------------------------------------------------------------------
def _segment_member(flat, lo, hi, target, iters: int):
    """Vectorized binary search: is `target` in sorted flat[lo:hi)?

    All of lo/hi/target may be arbitrary (broadcast-compatible) shapes.
    `iters` must be >= ceil(log2(max segment length)) + 1 (static).
    """
    shape = jnp.broadcast_shapes(lo.shape, hi.shape, target.shape)
    lo = jnp.broadcast_to(lo, shape)
    hi = jnp.broadcast_to(hi, shape)
    hi0 = hi

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        val = flat[mid]
        active = lo < hi
        go_right = active & (val < target)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    found = (lo < hi0) & (flat[jnp.minimum(lo, flat.shape[0] - 1)] == target)
    return found


def _row_slices(flat, start, width: int):
    """[rows, width] windows flat[start[r] : start[r] + width], one
    contiguous slice per row rather than `width` scalar indices.  A
    slice that would run past the array reads as -1 (never a vertex)
    instead of being clamped, so no window is silently shifted."""
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
    return jax.lax.gather(flat, start[:, None], dn, slice_sizes=(width,),
                          mode=jax.lax.GatherScatterMode.FILL_OR_DROP,
                          fill_value=-1)


def candidate_window(flat, indptr, degrees, base, width: int, *,
                     labs=None, label=None, pallas: str = "0"):
    """(cand, ok) [rows, width]: the candidate window at each row's
    `base`, the `width` entries of the flat CSR array from the base
    row's offset, and which of them lie inside the row.  Each row is
    gathered as one contiguous slice: by the Pallas row gather (`pallas`
    "1" compiled for the TPU, "interpret" in the Pallas interpreter), or
    by XLA slices ("0").  With a `label`
    requirement the window comes from the base row's per-label segment
    (lab_flat is grouped by destination label, each segment sorted by
    id), so the label mask is applied by construction BEFORE any
    membership test — the rows that reach the intersection kernels are
    already label-pruned.

    Every slice lies inside its array: each offset, the sentinel row
    n's included, leaves at least max-degree ≥ width entries after it.
    The CSR build pads `indices`, and so `lab_flat`, with max-degree
    sentinels past the last row (`GraphCSR.from_edges`); a live view
    keeps a window-wide tail past its patch region (`live/overlay.py`)."""
    if label is not None:
        _, lab_starts, lab_lens, src = labs
        start, length = lab_starts[base, label], lab_lens[base, label]
    else:
        src, start, length = flat, indptr[base], degrees[base]
    if pallas == "0":
        cand = _row_slices(src, start, width)
    else:
        from ..kernels.ops import window_gather
        cand = window_gather(src, start, width=width,
                             interpret=pallas == "interpret")
    ok = jnp.arange(width)[None, :] < length[:, None]
    return cand, ok


def _bs_iters(max_degree: int) -> int:
    return max(1, math.ceil(math.log2(max(max_degree, 2))) + 1)


@dataclass(frozen=True)
class ExecutorConfig:
    capacity: int = 1 << 15          # frontier rows per level
    dynamic_base: bool = True        # per-row min-degree base predecessor
    # Fused Pallas level-expansion kernel (the TPU hot path).  None =
    # auto: True on TPU backends, False elsewhere (the portable
    # binary-search path).  True on a backend without Mosaic runs the
    # Pallas interpreter — bit-exact but slow, for parity tests — and
    # says so in the fingerprint (`pallas=interpret`).
    use_pallas: bool | None = None
    # Degree-bucketed expansion (§Perf, graphpi cell): ((width, frac), ...)
    # ascending widths; rows whose base degree fits a narrower window are
    # compacted into a frac·capacity sub-frontier and gathered at that
    # width, so power-law max-degree padding is paid only by the rows
    # that need it.  None = single max-degree window (paper-faithful
    # baseline behaviour); internally that is the degenerate one-bucket
    # layout ((W, 1.0),) — both run the same expansion core.
    degree_buckets: tuple | None = None

    def resolve_use_pallas(self) -> bool:
        if self.use_pallas is None:
            return jax.default_backend() == "tpu"
        return self.use_pallas

    def pallas_mode(self) -> str:
        """How the level expansion runs: "0" portable, "1" the Mosaic
        kernel compiled for the TPU, "interpret" the Pallas interpreter
        (the fused path on any other backend)."""
        if not self.resolve_use_pallas():
            return "0"
        return "1" if jax.default_backend() == "tpu" else "interpret"

    def fingerprint(self) -> str:
        """Stable string of the facets baked into a jitted count program
        (capacity, base selection, RESOLVED pallas path, bucket layout).
        Safe to persist: equal strings ⟺ the same compiled program
        modulo graph/plan, across processes and serving replicas."""
        buckets = "none" if self.degree_buckets is None else ";".join(
            f"{int(w)}:{float(f):.6g}" for w, f in self.degree_buckets)
        return (f"cap={self.capacity},dyn={int(self.dynamic_base)},"
                f"pallas={self.pallas_mode()},"
                f"buckets={buckets}")


def auto_buckets(graph, *, small: int = 128, mid: int = 1024,
                 stats: GraphStats | None = None):
    """Degree buckets from the graph's degree distribution.

    Legacy layout (`stats=None`): fractions are sized ~4× above the
    empirical vertex-count shares so bucket overflow (→ capacity
    escalation) is rare — a blanket margin that over-allocates the tail
    buckets on most graphs.

    Model layout (`stats=GraphStats`): fractions come from the perf
    model's *predicted frontier occupancy*
    (`perf_model.predicted_frontier_occupancy`) — the edge-weighted
    share of rows whose base lands above each width threshold, times
    the model's clustering amplification (p2/p1, clamped).  Both
    layouts share the 1/64 floor and run the identical expansion core;
    the flag only moves capacity between buckets, never correctness
    (any layout counts exactly — tests/test_executor_buckets.py)."""
    W = max(graph.max_degree, 1)
    if W <= small:
        return None
    deg = graph.degrees

    if stats is not None:
        from .perf_model import predicted_frontier_occupancy

        def frac(lo: int) -> float:
            return min(1.0, max(
                predicted_frontier_occupancy(stats, deg, lo), 1 / 64))
    else:
        n = max(len(deg), 1)

        def frac(lo: int) -> float:
            return min(1.0, max(4.0 * float((deg > lo).sum()) / n, 1 / 64))

    out = [(small, 1.0)]
    if W > mid:
        out.append((mid, frac(small)))
        out.append((W, frac(mid)))
    else:
        out.append((W, frac(small)))
    return tuple(out)


def _bucket_layout(cfg: ExecutorConfig, W: int) -> tuple:
    """Normalized ((width, frac), ...) bucket layout; None collapses to
    the degenerate single max-degree window so there is exactly ONE
    expansion path."""
    buckets = cfg.degree_buckets
    if buckets is None:
        return ((W, 1.0),)
    buckets = tuple((min(int(w), W), float(f)) for (w, f) in buckets)
    if buckets[-1][0] < W:
        buckets = buckets + ((W, buckets[-1][1]),)
    return buckets


def _bucket_ranges(buckets: tuple, C: int):
    """(index, width, cap, lower degree bound, is_last) of each bucket at
    frontier capacity C."""
    lo = 0
    for bi, (w, f) in enumerate(buckets):
        yield bi, w, max(int(C * f), 8), lo, bi == len(buckets) - 1
        lo = w


def fill_slots(plan: MatchingPlan, W: int,
               cfg: ExecutorConfig) -> list[tuple[str, int, int]]:
    """(scope, cap, width) of every candidate window the count program
    may launch, in the order of the rows of its `fill` output: each
    expansion level (`level{i}`), then each IEP union (`iep{k}`), each
    over the bucket layout.  The scope is the window's `jax.named_scope`
    (`level{i}/w{width}`), so its device ops carry it in a profile."""
    steps = [f"level{i}" for i in range(1, plan.depth)]
    if plan.iep is not None:
        steps += [f"iep{k}" for k in range(len(plan.iep.unions))]
    ranges = list(_bucket_ranges(_bucket_layout(cfg, W), cfg.capacity))
    return [(f"{step}/w{w}", cap, w) for step in steps
            for _, w, cap, _, _ in ranges]


def window_steps(plan: MatchingPlan) -> int:
    """Window steps of a count program: each expansion level, and the
    IEP tail (all its unions consume one frontier) when there is one."""
    return plan.depth - 1 + (plan.iep is not None)


def need_parts(graph, plan: MatchingPlan, cfg: ExecutorConfig, W: int,
               ) -> tuple[int, list[tuple[int | None, np.ndarray]]]:
    """What each root adds to the `needed` a count program reads back,
    from the host CSR: (steps, parts), where each part is (bucket index,
    rows) and rows[v] is root v's share of one bucket window's rows, or
    of a level's frontier when the index is None.  A span's part is the
    sum over its roots; a bucket part enters `needed` scaled by C / cap,
    as `scaled_need` does.

    The host enumerates the frontier level by level with the program's
    membership tests and comparisons, from the roots on, and counts each
    window step's rows per bucket (base picked as `pick_base` picks it)
    and each frontier it expands.  It expands a level only while the
    candidate entries that costs (the rows' base degrees) fit the slots
    one dispatch streams at a window step, so the host holds no more
    than the program's own windows; `steps` is how many of the plan's
    `window_steps` it counted.

    The maximum of the parts is a lower bound on the device's `needed`,
    exact where every step is counted.  Labeled plans get no parts."""
    if plan.vlabels is not None:
        return 0, []
    n = graph.n
    deg = np.asarray(graph.degrees, dtype=np.int64)
    indptr = np.asarray(graph.indptr, dtype=np.int64)
    indices = np.asarray(graph.indices, dtype=np.int64)
    layout = list(_bucket_ranges(_bucket_layout(cfg, W), cfg.capacity))
    budget = sum(cap * width for _, width, cap, _, _ in layout)
    # the last level keeps a frontier for the IEP tail, else it counts
    last_frontier = plan.depth - (1 if plan.iep is not None else 2)
    parts, edge_keys = [], None

    def windows(i):
        """Predecessor columns of each window at loop position i."""
        if i < plan.depth:
            return [plan.preds[i]]
        return list(plan.iep.unions) if plan.iep is not None else []

    def pick_base(emb, preds):
        # as the program's `pick_base`: the first lowest degree, or the last
        pv = np.stack([emb[p] for p in preds])
        if cfg.dynamic_base and len(preds) > 1:
            sel = np.argmin(deg[pv], axis=0)
            return np.take_along_axis(pv, sel[None, :], axis=0)[0]
        return pv[-1]

    def window(base):
        """(row, neighbour) of every entry of each base's CSR row."""
        db = deg[base]
        row = np.repeat(np.arange(len(base)), db)
        return row, indices[np.repeat(indptr[base], db) + np.arange(len(row))
                            - np.repeat(np.cumsum(db) - db, db)]

    def is_edge(u, v):
        nonlocal edge_keys
        if edge_keys is None:
            src, dst = window(np.arange(n))
            edge_keys = np.sort(src * n + dst)
        at = np.minimum(np.searchsorted(edge_keys, u * n + v),
                        len(edge_keys) - 1)
        return edge_keys[at] == u * n + v

    def expand(emb, i, base):
        """Level i's frontier: each row's neighbours of its base that pass
        level i's comparisons and are adjacent to every predecessor."""
        row, cand = window(base)
        keep = np.ones(len(row), dtype=bool)
        for col, d in tuple(plan.restr[i]) + tuple(
                (j, 0) for j in plan.neqs[i]):
            ev = emb[col][row]
            keep &= (cand > ev) if d > 0 else (cand < ev) if d < 0 \
                else (cand != ev)
        row, cand = row[keep], cand[keep]
        # a lone predecessor is the base itself: its members all pass
        for p in plan.preds[i] if len(plan.preds[i]) > 1 else ():
            keep = is_edge(emb[p][row], cand)
            row, cand = row[keep], cand[keep]
        return [c[row] for c in emb] + [cand]

    emb, steps = [np.arange(n, dtype=np.int64)], 0
    for i in range(1, window_steps(plan) + 1):
        for preds in windows(i):
            base = pick_base(emb, preds)
            db = deg[base]
            for bi, width, _, lo, is_last in layout:
                rows = (db > lo) & (is_last | (db <= width))
                parts.append((bi, np.bincount(emb[0][rows], minlength=n)))
        steps = i
        if i > last_frontier or db.sum() > budget:
            break
        emb = expand(emb, i, base)
        parts.append((None, np.bincount(emb[0], minlength=n)))
    return steps, parts


@dataclass
class CountResult:
    count: int
    overflowed: bool
    max_needed: int                  # max frontier rows needed at any level


@dataclass
class CountState:
    """Resumable progress of one chunked count (`Matcher.count_partial`).

    The outer vertex loop is a work stack of ``(start, end, capacity)``
    spans; a preempted count is exactly this stack plus the raw running
    totals.  `total` is the RAW embedding sum — the IEP divisor (and the
    naive-mode |Aut| division, `CacheEntry.count_partial`) apply once at
    completion, so partial segments never lose remainder bits."""

    spans: list                      # [(start, end, capacity)], LIFO
    chunk: int                       # resolved chunk width (span rebuilds)
    total: int = 0                   # raw sum, pre iep_divisor
    overflowed: bool = False
    max_needed: int = 0
    dispatches: int = 0              # kernel dispatches so far (all segments)


# --------------------------------------------------------------------------
# single-shard counting kernel (pure function of device arrays; jit-safe)
# --------------------------------------------------------------------------
def _make_count_fn(plan: MatchingPlan, W: int, iters: int,
                   cfg: ExecutorConfig):
    """Returns count(indptr, degrees, flat, v0) -> (count i64, needed
    i32, fill i64[K, 2]) — or, for labeled plans, count(indptr, degrees,
    flat, labs, v0) where `labs` = (vlabels [n+1], lab_starts [n+1, L],
    lab_lens [n+1, L], lab_flat) are the device per-label CSR views (see
    `device_graph`).

    `fill` row k belongs to window k of `fill_slots(plan, W, cfg)`: the
    rows that window holds (0 where its bucket is empty and the window
    is skipped) and their valid entries, the sum of their base degrees
    (or label-segment lengths).  It is computed on every run, so a
    traced run executes the program an untraced one does.

    `W` = candidate-window width (graph max degree), static.
    `degrees` must be padded to [n+1] with 0 at index n (sentinel).
    """
    n = plan.n
    depth = plan.depth
    C = cfg.capacity
    use_pallas = cfg.resolve_use_pallas()
    interpret = cfg.pallas_mode() == "interpret"
    # Static per-position label requirements (None = wildcard / unlabeled).
    vlabels = plan.vlabels or (None,) * n

    buckets = _bucket_layout(cfg, W)

    # the window gather's kernel runs where Mosaic compiles it; the
    # interpreter takes a host callback per row DMA, so the fused path's
    # CPU parity runs gather their windows as XLA slices (the kernel's
    # own parity is tests/test_window_gather.py)
    gather_window = partial(
        candidate_window, pallas="1" if cfg.pallas_mode() == "1" else "0")

    def base_degrees(degrees, pv, *, labs=None, label=None):
        """Candidate-set size per predecessor: the full degree, or the
        per-label segment length when the target position is labeled."""
        if label is not None:
            _, _, lab_lens, _ = labs
            return lab_lens[pv, label]
        return degrees[pv]

    def pick_base(emb, degrees, preds, *, labs=None, label=None):
        pv = emb[:, jnp.asarray(preds)]            # [C, P]
        if not cfg.dynamic_base or len(preds) == 1:
            return pv[:, -1]
        dg = base_degrees(degrees, pv, labs=labs, label=label)
        sel = jnp.argmin(dg, axis=1)
        return jnp.take_along_axis(pv, sel[:, None], axis=1)[:, 0]

    def level_extras(i):
        """Restriction + injectivity comparisons at loop position i as a
        uniform ((emb column, dir), ...) spec; dir ∈ {+1: >, -1: <, 0: !=}."""
        return tuple(plan.restr[i]) + tuple((j, 0) for j in plan.neqs[i])

    def expand_core(emb, base, valid, preds, extras,
                    indptr, degrees, flat, width, *, want_counts=False,
                    labs=None, label=None):
        """THE per-level admissibility core (shared by every path).

        Gathers the candidate window at `base`, tests membership in
        every predecessor neighborhood, and applies the restriction /
        injectivity comparisons.  Returns (cand, mask) — or per-row
        int32 counts when `want_counts` (last enumeration level and
        IEP-tail cardinalities).

        Pallas path: everything above is ONE fused kernel pass over the
        candidate matrix (kernels.intersect.level_expand_pallas), with
        the predecessor loop on the innermost grid dimension — one HBM
        round trip per level where the portable path does one compare /
        mask pass per predecessor, restriction, and != constraint.  The
        base's own membership test is redundant but keeps the kernel
        branch-free under the dynamic-base selection.

        Labeled positions change ONLY the gather (per-label segment of
        the base row); membership keeps walking the plain sorted rows on
        both paths, so portable and fused stay bit-identical.
        """
        cand, ok = gather_window(flat, indptr, degrees, base, width,
                                 labs=labs, label=label)
        mask = ok & valid[:, None]
        if use_pallas and len(preds) > 1:
            from ..kernels.ops import level_expand

            # the kernel gathers each predecessor's neighbor window from
            # the flat CSR array itself (scalar-prefetched offsets +
            # in-grid DMA) — nothing here materializes a [P, B, W] stack
            us = emb[:, jnp.asarray(preds)].T                      # [P, B]
            res = level_expand(
                cand, flat, indptr[us], degrees[us],
                emb[:, jnp.asarray([c for c, _ in extras])] if extras
                else None,
                mask,
                dirs=tuple(d for _, d in extras), count=want_counts,
                interpret=interpret,
            )
            return res if want_counts else (cand, res)
        if len(preds) > 1:
            # membership in every predecessor's neighborhood (the base's
            # own test is redundant but keeps the mask branch-free under
            # the dynamic-base selection)
            for p in preds:
                u = emb[:, p]
                lo = indptr[u][:, None]
                hi = lo + degrees[u][:, None]
                mask &= _segment_member(flat, lo, hi, cand, iters)
        for (col, d) in extras:
            ev = emb[:, col][:, None]
            if d > 0:
                mask &= cand > ev
            elif d < 0:
                mask &= cand < ev
            else:
                mask &= cand != ev
        if want_counts:
            return mask.sum(axis=1).astype(jnp.int32)
        return cand, mask

    def select_rows(rowmask, cap, db):
        """Compact indices of rows where rowmask → (sel_idx [cap] with C as
        the drop sentinel, sub_valid [cap], sub_total, fill), where fill
        is the (rows, valid entries) of the window: the first `cap` such
        rows and the sum of their base degrees `db`."""
        pos = jnp.cumsum(rowmask) - 1
        total = (pos[-1] + 1).astype(jnp.int32)
        out_idx = jnp.where(rowmask, jnp.minimum(pos, cap), cap)
        sel = jnp.full((cap + 1,), C, dtype=jnp.int32)
        sel = sel.at[out_idx].set(jnp.arange(C, dtype=jnp.int32),
                                  mode="drop")
        sub_valid = jnp.arange(cap) < total
        fill = jnp.stack([
            jnp.minimum(total, cap).astype(jnp.int64),
            jnp.sum(jnp.where(rowmask & (pos < cap), db, 0),
                    dtype=jnp.int64)])
        return sel[:cap], sub_valid, total, fill

    def scaled_need(sub_total, cap):
        """Escalation units: sub_total scaled to full-capacity terms so the
        driver's capacity doubling also doubles every bucket."""
        st = sub_total.astype(jnp.int64)
        return ((st * C + cap - 1) // cap).astype(jnp.int32)

    def expand_level(i, emb, valid, needed, indptr, degrees, flat, labs,
                     fills):
        """One level of frontier expansion over the bucket layout; each
        bucket's window fill is appended to `fills`.

        Returns (new_emb, new_valid, needed) — or, at the last
        enumeration level, (count_contribution, None, needed)."""
        preds = plan.preds[i]
        extras = level_extras(i)
        label = vlabels[i]
        base_all = pick_base(emb, degrees, preds, labs=labs, label=label)
        db = base_degrees(degrees, base_all, labs=labs, label=label)
        last_enum = (plan.iep is None) and (i == n - 1)
        parent = jnp.zeros((C + 1,), dtype=jnp.int32)
        newcol = jnp.zeros((C + 1,), dtype=jnp.int32)
        offset = jnp.asarray(0, jnp.int32)
        total_cnt = jnp.asarray(0, jnp.int64)
        for bi, width, cap, lo, is_last in _bucket_ranges(buckets, C):
            with jax.named_scope(f"level{i}/w{width}"):
                rowmask = valid & (db > lo)
                if not is_last:
                    rowmask &= db <= width
                sel_idx, sub_valid, sub_total, fill = select_rows(
                    rowmask, cap, db)
                fills.append(fill)
                needed = jnp.maximum(needed, scaled_need(sub_total, cap))

                def bucket_count(sel_idx=sel_idx, sub_valid=sub_valid,
                                 width=width):
                    cnts = expand_core(
                        jnp.take(emb, sel_idx, axis=0, mode="clip")[:, :i],
                        jnp.take(base_all, sel_idx, mode="clip"),
                        sub_valid, preds, extras, indptr, degrees, flat,
                        width, want_counts=True, labs=labs, label=label,
                    )
                    return jnp.sum(cnts, dtype=jnp.int64)

                def bucket_expand(carry, sel_idx=sel_idx,
                                  sub_valid=sub_valid, width=width, cap=cap):
                    parent, newcol, offset = carry
                    cand, mask = expand_core(
                        jnp.take(emb, sel_idx, axis=0, mode="clip")[:, :i],
                        jnp.take(base_all, sel_idx, mode="clip"),
                        sub_valid, preds, extras, indptr, degrees, flat,
                        width, labs=labs, label=label,
                    )
                    # stream-compact surviving (row, cand) pairs behind
                    # `offset`
                    flat_mask = mask.reshape(-1)
                    pos = jnp.cumsum(flat_mask) - 1
                    bucket_total = (pos[-1] + 1).astype(jnp.int32)
                    out_idx = jnp.where(flat_mask,
                                        jnp.minimum(offset + pos, C), C)
                    # each window entry's frontier row, broadcast along
                    # the row (a gather by entry would cost as much as
                    # a scalar window gather)
                    parent = parent.at[out_idx].set(
                        jnp.broadcast_to(sel_idx[:, None],
                                         (cap, width)).reshape(-1),
                        mode="drop")
                    newcol = newcol.at[out_idx].set(cand.reshape(-1),
                                                    mode="drop")
                    return parent, newcol, offset + bucket_total

                # an empty bucket contributes nothing: skip its window
                # gather, membership and compaction.  A wide bucket is
                # empty in most chunks and its fixed-shape work would
                # dominate them; without the skip the served mico-syn
                # triangle does not fit chip_smoke.py's run (PERF.md §5)
                if last_enum:
                    total_cnt += jax.lax.cond(
                        sub_total > 0, bucket_count,
                        lambda: jnp.asarray(0, jnp.int64))
                else:
                    parent, newcol, offset = jax.lax.cond(
                        sub_total > 0, bucket_expand, lambda c: c,
                        (parent, newcol, offset))
        if last_enum:
            return total_cnt, None, needed
        new_emb = jnp.concatenate(
            [jnp.take(emb, parent[:C], axis=0, mode="clip")[:, :i],
             newcol[:C, None]], axis=1,
        )
        new_valid = jnp.arange(C) < offset
        needed = jnp.maximum(needed, offset)
        return new_emb, new_valid, needed

    def iep_card_fused(sub_emb, sub_base, sub_valid, U,
                       indptr, degrees, flat, width):
        """One IEP-term cardinality — |window ∩ (∩_q N(v_q))| minus the
        prefix-vertex corrections — in a SINGLE fused kernel pass: the
        already-assigned prefix vertices ride along as negatively-
        weighted candidate columns (`neg_from`), so the kernel's signed
        popcount returns raw − corr directly (DESIGN.md §4) instead of
        one binary-search sweep per prefix position."""
        from ..kernels.ops import level_expand

        cand, ok = gather_window(flat, indptr, degrees, sub_base, width)
        comb = jnp.concatenate([cand, sub_emb], axis=1)
        cvalid = jnp.concatenate(
            [ok & sub_valid[:, None],
             jnp.broadcast_to(sub_valid[:, None], sub_emb.shape)], axis=1)
        us = sub_emb[:, jnp.asarray(U)].T                          # [P, B]
        signed = level_expand(
            comb, flat, indptr[us], degrees[us], None, cvalid,
            dirs=(), count=True, neg_from=width, interpret=interpret,
        )
        return signed.astype(jnp.int64)

    def iep_value(emb, valid, indptr, degrees, flat, fills):
        """Per-row IEP count over the folded tail (int64), with bucketed
        union-window gathers through the shared expansion core.  On the
        Pallas path each (union, bucket) cardinality — including the
        prefix corrections — is one fused kernel pass.  Each window's
        fill is appended to `fills`."""
        iep = plan.iep
        cards = []
        needed_extra = jnp.asarray(0, jnp.int32)

        def bucket_card(U, base, sel_idx, sub_valid, width, card):
            sub_emb = jnp.take(emb, sel_idx, axis=0, mode="clip")
            sub_base = jnp.take(base, sel_idx, mode="clip")
            if use_pallas:
                val = iep_card_fused(
                    sub_emb, sub_base, sub_valid, U,
                    indptr, degrees, flat, width)
            else:
                raw = expand_core(
                    sub_emb, sub_base, sub_valid, U, (),
                    indptr, degrees, flat, width, want_counts=True,
                ).astype(jnp.int64)
                # subtract already-assigned prefix vertices inside
                # the intersection (injectivity w.r.t. outer loops)
                corr = jnp.zeros_like(raw)
                for j in range(depth):
                    vj = sub_emb[:, j]
                    inside = sub_valid
                    for q in U:
                        u = sub_emb[:, q]
                        inside &= _segment_member(
                            flat, indptr[u], indptr[u] + degrees[u],
                            vj, iters
                        )
                    corr += inside.astype(jnp.int64)
                val = raw - corr
            return card.at[sel_idx].add(
                jnp.where(sub_valid, val, 0), mode="drop")

        for k, U in enumerate(iep.unions):
            base = pick_base(emb, degrees, U)
            db = degrees[base]
            card = jnp.zeros((C,), jnp.int64)
            for bi, width, cap, lo, is_last in _bucket_ranges(buckets, C):
                with jax.named_scope(f"iep{k}/w{width}"):
                    rowmask = valid & (db > lo)
                    if not is_last:
                        rowmask &= db <= width
                    sel_idx, sub_valid, sub_total, fill = select_rows(
                        rowmask, cap, db)
                    fills.append(fill)
                    needed_extra = jnp.maximum(needed_extra,
                                               scaled_need(sub_total, cap))
                    # an empty bucket adds nothing (see expand_level)
                    card = jax.lax.cond(
                        sub_total > 0,
                        partial(bucket_card, U, base, sel_idx, sub_valid,
                                width),
                        lambda c: c, card)
            cards.append(card)
        val = jnp.zeros((C,), dtype=jnp.int64)
        for coeff, idxs in iep.terms:
            term = jnp.full((C,), coeff, dtype=jnp.int64)
            for u in idxs:
                term = term * cards[u]
            val = val + term
        return jnp.where(valid, val, 0), needed_extra

    def count_impl(indptr, degrees, flat, labs, v0):
        emb = v0[:, None].astype(jnp.int32)                    # [T, 1]
        valid = v0 < (indptr.shape[0] - 1)
        if vlabels[0] is not None:
            # root label mask: v0 is padded with the sentinel n, and the
            # device vlabels array carries -1 there, so sentinels never
            # match a real label
            valid &= labs[0][v0] == vlabels[0]
        # pad/crop the initial frontier to capacity C
        T = emb.shape[0]
        if T < C:
            emb = jnp.pad(emb, ((0, C - T), (0, 0)))
            valid = jnp.pad(valid, (0, C - T))
        needed = jnp.asarray(T, dtype=jnp.int32)
        fills = []

        def fill():
            if not fills:
                return jnp.zeros((0, 2), jnp.int64)
            return jnp.stack(fills)

        for i in range(1, depth):
            out, new_valid, needed = expand_level(
                i, emb, valid, needed, indptr, degrees, flat, labs, fills)
            if new_valid is None:          # last enumeration level
                return out, needed, fill()
            emb, valid = out, new_valid
        if plan.iep is None:
            # depth-1 == 0: single-vertex pattern — count valid v0 rows
            return jnp.sum(valid, dtype=jnp.int64), needed, fill()
        vals, need2 = iep_value(emb, valid, indptr, degrees, flat, fills)
        return jnp.sum(vals), jnp.maximum(needed, need2), fill()

    if plan.vlabels is None:
        # unlabeled plans keep the historical 4-arg signature (AOT blobs,
        # shard_map specs, dryrun all depend on it)
        def count(indptr, degrees, flat, v0):
            return count_impl(indptr, degrees, flat, None, v0)
        return count

    def count_labeled(indptr, degrees, flat, labs, v0):
        return count_impl(indptr, degrees, flat, labs, v0)
    return count_labeled


# --------------------------------------------------------------------------
# public host-side drivers
# --------------------------------------------------------------------------
class DeviceGraph(NamedTuple):
    """Resident device arrays for one graph.  The first three fields are
    the historical (indptr, padded degrees, flat) triple; `labs` is the
    per-label view pytree (vlabels, lab_starts, lab_lens, lab_flat) for
    labeled graphs, or None."""

    indptr: object
    degrees: object
    flat: object
    labs: object = None


def device_graph(graph: GraphCSR) -> DeviceGraph:
    """Upload one graph to device memory (indptr, padded degrees, flat).

    Matchers accept the returned tuple via ``arrays=`` so long-lived
    callers (the query engine) keep ONE resident copy of the CSR shared
    by every cached matcher instead of re-uploading per pattern.

    The flat indices array is padded ONCE here with never-matching
    sentinels to a whole number of gather tiles, so the kernels' aligned
    tile DMAs (DESIGN.md §4) — the fused level kernel's predecessor
    rows and the candidate-window gather — read a free [F/128, 128]
    view of it and no kernel call re-pads the resident graph.

    Labeled graphs additionally upload the per-label CSR view: vlabels
    padded to [n+1] with -1 (the frontier's sentinel root n never
    matches a label), lab_starts/lab_lens padded with an all-empty row n
    for the same reason.  lab_flat feeds the candidate-window gather, so
    it is padded to whole tiles as well."""
    from ..kernels.ops import flat_gather_pad

    def tiled(a):
        return jnp.asarray(np.concatenate([
            a, np.full(flat_gather_pad(len(a)), np.iinfo(np.int32).max,
                       dtype=np.int32)]))

    degrees = np.concatenate([graph.degrees, np.zeros(1, dtype=np.int32)])
    labs = None
    if graph.labels is not None:
        lv = graph.label_view
        L = graph.n_labels
        vlabels = np.concatenate(
            [graph.labels, np.full(1, -1, dtype=np.int32)])
        lab_starts = np.concatenate(
            [lv.starts, np.zeros((1, L), dtype=np.int32)])
        lab_lens = np.concatenate(
            [lv.lens, np.zeros((1, L), dtype=np.int32)])
        labs = (
            jnp.asarray(vlabels),
            jnp.asarray(lab_starts),
            jnp.asarray(lab_lens),
            tiled(lv.flat),
        )
    return DeviceGraph(
        jnp.asarray(graph.indptr),
        jnp.asarray(degrees),
        tiled(graph.indices),
        labs,
    )


def _labs_of(arrays):
    """Label-view pytree of a DeviceGraph (None for legacy 3-tuples)."""
    return arrays[3] if len(arrays) > 3 else None


class Matcher:
    """Reusable single-device matcher: compile once, count many times.

    Benchmarks construct one Matcher per configuration and call
    ``warmup()`` before timing so compile time never pollutes the
    measurement (the paper excludes compilation time too)."""

    MAX_CAPACITY = 1 << 22   # escalation ceiling (frontier RAM bound)

    def __init__(self, graph: GraphCSR, plan: MatchingPlan,
                 cfg: ExecutorConfig | None = None, *, arrays=None):
        self.graph = graph
        self.plan = plan
        self.cfg = cfg or ExecutorConfig()
        self._W = max(graph.max_degree, 1)
        self._fns: dict[int, object] = {}     # capacity -> jitted count_fn
        self._arrays = arrays if arrays is not None else device_graph(graph)
        self._labeled = plan.vlabels is not None
        if self._labeled and _labs_of(self._arrays) is None:
            raise ValueError(
                f"labeled pattern {plan.pattern.name!r} cannot run against "
                f"unlabeled graph {graph.name!r}")
        self._capacity = self.cfg.capacity    # sticky escalated capacity
        self._need = None                     # prefix sums of need_parts
        self._steps = 0                       # window steps they cover
        self._planned: dict[tuple, tuple] = {}  # (capacity, chunk) -> spans

    def _call_args(self):
        """Positional args ahead of v0 — labeled plans append the label
        views so the jitted signature matches _make_count_fn."""
        indptr, degrees, flat = self._arrays[:3]
        if self._labeled:
            return (indptr, degrees, flat, _labs_of(self._arrays))
        return (indptr, degrees, flat)

    def _fn(self, capacity: int):
        if capacity not in self._fns:
            self._fns[capacity] = jax.jit(_make_count_fn(
                self.plan, self._W, _bs_iters(self._W),
                replace(self.cfg, capacity=capacity),
            ))
        return self._fns[capacity]

    def _slots(self, capacity: int) -> list:
        return fill_slots(self.plan, self._W,
                          replace(self.cfg, capacity=capacity))

    def _fill_attrs(self, capacity: int, fill) -> dict:
        """Window fill of one dispatch, for its `executor.dispatch` span:
        per launched window [scope, rows, valid entries, slots streamed
        (cap x width)] under `windows`, and their totals."""
        windows = [[scope, rows, valid, cap * width]
                   for (scope, cap, width), (rows, valid)
                   in zip(self._slots(capacity), fill.tolist()) if rows]
        return {"rows_live": sum(w[1] for w in windows),
                "entries_valid": sum(w[2] for w in windows),
                "entries_streamed": sum(w[3] for w in windows),
                "windows": windows}

    def _need_sums(self) -> list:
        """need_parts prefix-summed over root ids, once per matcher."""
        if self._need is None:
            self._steps, parts = need_parts(self.graph, self.plan,
                                            self.cfg, self._W)
            self._need = [(b, np.concatenate([[0], np.cumsum(rows)]))
                          for b, rows in parts]
        return self._need

    def predicted_steps(self) -> int:
        """How many of the plan's `window_steps` the host plan covers."""
        self._need_sums()
        return self._steps

    def _limits(self, capacity: int) -> list:
        """(prefix sums, most a span may hold) of each need part at
        `capacity`: a bucket's rows fit its cap, the frontier fits C."""
        caps = [cap for _, _, cap, _, _ in _bucket_ranges(
            _bucket_layout(self.cfg, self._W), capacity)]
        return [(pre, capacity if b is None else caps[b])
                for b, pre in self._need_sums()]

    def predicted(self, start: int, end: int, capacity: int,
                  width: int) -> int:
        """The host's prediction of the `needed` a dispatch of roots
        [start, end) at `capacity` and v0 width `width` reads back: a
        lower bound, exact for the plans `need_parts` covers."""
        need = width
        for pre, lim in self._limits(capacity):
            t = int(pre[end] - pre[start])
            need = max(need, -(-t * capacity // lim))
        return need

    def planned_spans(self, capacity: int, chunk: int) -> list:
        """The span stack a fresh count starts from: greedy cuts of the
        root id range, each the longest of at most `chunk` roots whose
        predicted need fits `capacity` (a root over it alone), ascending
        so the stack pops the last first.  Where fixed `chunk`-wide
        spans would not overflow, these are those spans."""
        key = (capacity, chunk)
        if key not in self._planned:
            n = self.graph.n
            limits = self._limits(capacity)
            spans, s = [], 0
            while s < n:
                e = min(s + chunk, n)
                for pre, lim in limits:
                    e = min(e, int(np.searchsorted(
                        pre, pre[s] + lim, side="right")) - 1)
                spans.append((s, max(e, s + 1), capacity))
                s = spans[-1][1]
            self._planned[key] = tuple(spans)
        return list(self._planned[key])

    def warmup(self, *, chunk: int | None = None) -> None:
        """Compile against a sentinel frontier.  Pass the same `chunk`
        later given to :meth:`count`, or the trace compiled here (v0
        shape = chunk width) is not the one counting will use."""
        width = min(chunk or self.cfg.capacity, self.cfg.capacity)
        v0 = jnp.full((width,), self.graph.n, dtype=jnp.int32)
        with jax.enable_x64(True):
            jax.block_until_ready(
                self._fn(self.cfg.capacity)(*self._call_args(), v0))

    # --------------------------------------------------- AOT persistence
    def export_bytes(self, *, chunk: int | None = None) -> bytes:
        """Serialize the base-capacity count program ahead-of-time
        (`jax.export` over the same (capacity, chunk-width) trace that
        :meth:`warmup` compiles).  A fresh process feeds the bytes to
        :meth:`install_exported` and skips Python re-tracing entirely;
        escalated capacities still JIT live (they are rare retry paths).
        """
        width = min(chunk or self.cfg.capacity, self.cfg.capacity)
        v0 = jnp.full((width,), self.graph.n, dtype=jnp.int32)
        with jax.enable_x64(True):
            exported = jax.export.export(self._fn(self.cfg.capacity))(
                *self._call_args(), v0)
        return exported.serialize()

    def install_exported(self, data: bytes, *,
                         chunk: int | None = None) -> None:
        """Install a serialized AOT program as the base-capacity count
        fn.  Raises ValueError when the blob targets another platform,
        was traced against different array shapes, or lacks the window
        fill output — callers catch it and fall back to a fresh
        :meth:`warmup` JIT."""
        exported = jax.export.deserialize(data)
        backend = jax.default_backend()
        if backend not in exported.platforms:
            raise ValueError(
                f"AOT program exported for {exported.platforms}, running "
                f"on {backend!r}")
        width = min(chunk or self.cfg.capacity, self.cfg.capacity)
        v0 = jax.ShapeDtypeStruct((width,), jnp.int32)
        want = tuple(
            tuple(a.shape)
            for a in jax.tree_util.tree_leaves((*self._call_args(), v0))
        )
        got = tuple(tuple(a.shape) for a in exported.in_avals)
        if got != want:
            raise ValueError(f"AOT input shapes {got} != expected {want}")
        outs = tuple(tuple(a.shape) for a in exported.out_avals)
        want = ((), (), (len(self._slots(self.cfg.capacity)), 2))
        if outs != want:
            raise ValueError(f"AOT output shapes {outs} != expected {want} "
                             f"(count, needed, window fill)")
        self._fns[self.cfg.capacity] = jax.jit(exported.call)

    def release(self) -> None:
        """Drop every compiled executable and device-array reference so
        LRU eviction actually frees HBM in long-lived serving processes
        (the resident graph shared via ``arrays=`` stays alive at its
        owner).  The matcher is unusable afterwards."""
        self._fns.clear()
        self._arrays = None
        self._need = None
        self._planned.clear()

    def rebind(self, arrays, *, graph=None) -> None:
        """Swap the resident device arrays for same-shaped replacements
        (a live-overlay epoch or compaction swap, src/repro/live/).

        Compiled count programs take the graph arrays as ARGUMENTS, so a
        same-shape swap replays every cached jit/AOT trace untouched —
        zero recompiles.  Any shape or dtype difference raises
        ValueError (the overlay genuinely grew); the caller must rebuild
        the matcher instead.  `graph` additionally swaps the host-side
        view, and must preserve the compiled gather window and vertex
        count (both are baked into the traces via `_W` and v0 padding).
        """
        if self._arrays is None:
            raise RuntimeError("matcher was released (evicted from cache)")
        old = jax.tree_util.tree_leaves(tuple(self._arrays))
        new = jax.tree_util.tree_leaves(tuple(arrays))
        if (len(old) != len(new)
                or any(tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype
                       for a, b in zip(old, new))):
            raise ValueError(
                "rebind needs identical array shapes/dtypes; the graph "
                "outgrew its fixed layout — rebuild the matcher")
        if graph is not None:
            if max(graph.max_degree, 1) != self._W:
                raise ValueError(
                    f"rebind window {max(graph.max_degree, 1)} != compiled "
                    f"window {self._W}")
            if graph.n != self.graph.n:
                raise ValueError(
                    f"rebind vertex count {graph.n} != {self.graph.n}")
            self.graph = graph
        self._arrays = arrays
        self._need = None
        self._planned.clear()

    def count(self, *, chunk: int | None = None) -> CountResult:
        """Chunked outer loop; a chunk that overflows capacity is bisected
        and retried (host-side adaptivity — the SPMD analogue of the
        paper's work splitting).  A single root that still overflows
        escalates to a doubled-capacity kernel so the count stays exact."""
        _, out = self.count_partial(chunk=chunk)
        return out

    def count_partial(self, state: CountState | None = None, *,
                      chunk: int | None = None,
                      max_dispatches: int | None = None,
                      ) -> tuple[CountState, CountResult | None]:
        """Run the chunked outer loop for up to `max_dispatches` kernel
        dispatches, then yield.  Returns ``(state, result)`` where
        `result` is None while spans remain — pass `state` back in to
        resume exactly where the loop stopped (same span stack, same
        raw totals; the final count is bit-identical to an
        uninterrupted :meth:`count`).  `max_dispatches=None` runs to
        completion (the exact :meth:`count` loop)."""
        if self._arrays is None:
            raise RuntimeError("matcher was released (evicted from cache)")
        graph, cfg = self.graph, self.cfg
        call_args = self._call_args()
        tr = get_tracer()
        planned = None
        if state is None:
            chunk = min(chunk or cfg.capacity, cfg.capacity)
            # spans: (start, end, capacity), cut from the host's
            # prediction of each span's need.  Start at the last count's
            # escalated capacity so warm repeats (the serve path) skip
            # the doomed undersized passes.
            state = CountState(
                spans=self.planned_spans(self._capacity, chunk),
                chunk=chunk,
            )
            planned = len(state.spans)
        chunk = state.chunk
        budget = None if max_dispatches is None else max(int(max_dispatches),
                                                         1)
        with jax.enable_x64(True), tr.span(
                "executor.count", depth=self.plan.depth,
                buckets=cfg.fingerprint(),
                resumed=state.dispatches > 0) as csp:
            spans = state.spans
            segment = 0
            while spans and (budget is None or segment < budget):
                s, e, cap = spans.pop()
                self._capacity = max(self._capacity, cap)
                width = min(chunk, cap)
                # the dispatch's host turn, split in its children: the
                # roots, the launch, and the blocking readbacks
                with tr.span("executor.dispatch", v0_start=s, v0_end=e,
                             capacity=cap, frontier=e - s) as dsp:
                    with tr.span("executor.roots"):
                        v0 = jnp.arange(s, e, dtype=jnp.int32)
                        if e - s < width:
                            v0 = jnp.pad(v0, (0, width - (e - s)),
                                         constant_values=graph.n)
                    # fn resolution inside the span: a cold capacity
                    # (escalation) compiles here, attributed to this
                    # dispatch
                    with tr.span("executor.launch"):
                        cnt, needed, fill = self._fn(cap)(*call_args, v0)
                    with tr.span("executor.readback"):
                        # the window fill crosses to the host only while
                        # tracing, in the transfer that brings `needed`
                        if tr.enabled:
                            needed, fill = jax.device_get((needed, fill))
                        # int() blocks until the device result is ready,
                        # so the dispatch span covers real compute time
                        needed = int(needed)
                    dsp.set(needed=needed)
                    if tr.enabled:
                        dsp.set(predicted=self.predicted(s, e, cap, width),
                                **self._fill_attrs(cap, fill))
                    segment += 1
                    state.dispatches += 1
                    state.max_needed = max(state.max_needed, needed)
                    if needed > cap:
                        if e - s > 1:
                            mid = (s + e) // 2
                            spans += [(s, mid, cap), (mid, e, cap)]
                            continue
                        if cap < self.MAX_CAPACITY:
                            spans.append((s, e, cap * 2))   # escalate
                            continue
                        state.overflowed = True  # cannot split/grow further
                    with tr.span("executor.readback"):
                        state.total += int(cnt)
            csp.set(dispatches=segment, max_needed=state.max_needed,
                    preempted=bool(spans))
            if planned is not None:
                csp.set(planned_spans=planned,
                        predicted_steps=self.predicted_steps(),
                        window_steps=window_steps(self.plan))
        if spans:
            return state, None
        return state, CountResult(count=state.total // self.plan.iep_divisor,
                                  overflowed=state.overflowed,
                                  max_needed=state.max_needed)


def count_embeddings(
    graph: GraphCSR,
    plan: MatchingPlan,
    cfg: ExecutorConfig | None = None,
    *,
    chunk: int | None = None,
) -> CountResult:
    """One-shot convenience wrapper around :class:`Matcher`."""
    return Matcher(graph, plan, cfg).count(chunk=chunk)


class ShardedMatcher:
    """Reusable multi-device matcher: compile once per capacity, count many.

    Distributed counting with outer-loop tasks striped over `axis`:
    device d takes v0 ∈ {d, d+P, ...} (fine-grained striping — DESIGN §3);
    with degree-descending relabeling this balances the power-law head.
    Each device scans its stripe in fixed-size chunks; if any chunk's
    frontier exceeds capacity, the whole pass is retried at doubled
    capacity (straggler-free SPMD analogue of the single-device
    bisection — every retry is a fresh collective-complete program).

    The jitted shard_map program is cached per capacity, so repeat
    counts (the serve path) pay zero compilation."""

    def __init__(self, graph: GraphCSR, plan: MatchingPlan, mesh,
                 *, axis: str = "data", cfg: ExecutorConfig | None = None,
                 chunk: int | None = None, arrays=None):
        self.graph = graph
        self.plan = plan
        self.mesh = mesh
        self.axis = axis
        self.cfg = cfg or ExecutorConfig()
        self._W = max(graph.max_degree, 1)
        self._iters = _bs_iters(self._W)
        self._arrays = arrays if arrays is not None else device_graph(graph)
        self._labeled = plan.vlabels is not None
        if self._labeled and _labs_of(self._arrays) is None:
            raise ValueError(
                f"labeled pattern {plan.pattern.name!r} cannot run against "
                f"unlabeled graph {graph.name!r}")
        self.chunk = chunk or max(64, self.cfg.capacity // 16)
        nshards = 1
        for ax in (axis,) if isinstance(axis, str) else axis:
            nshards *= mesh.shape[ax]
        per = math.ceil(graph.n / nshards)
        per = math.ceil(per / self.chunk) * self.chunk  # pad to chunk multiple
        self._per = per
        # striped: column-major so device d gets d, d+P, 2P+d, ...; laid
        # out over the mesh once here, as the shard_map consumes it
        from jax.sharding import NamedSharding, PartitionSpec

        v0 = np.full(nshards * per, graph.n, dtype=np.int32)
        v0[: graph.n] = np.arange(graph.n, dtype=np.int32)
        self._v0 = jax.device_put(v0.reshape(per, nshards).T.reshape(-1),
                                  NamedSharding(mesh, PartitionSpec(axis)))
        self._fns: dict[int, object] = {}     # capacity -> jitted shard fn
        self._capacity = self.cfg.capacity    # sticky escalated capacity

    def _call_args(self):
        indptr, degrees, flat = self._arrays[:3]
        return (indptr, degrees, flat, _labs_of(self._arrays))

    def _fn(self, capacity: int):
        if capacity not in self._fns:
            from jax.sharding import PartitionSpec as P

            count_fn = _make_count_fn(
                self.plan, self._W, self._iters,
                replace(self.cfg, capacity=capacity),
            )
            per, chunk, axis = self._per, self.chunk, self.axis
            labeled = self._labeled

            def shard_fn(indptr, degrees, flat, labs, v0_local):
                chunks = v0_local.reshape(per // chunk, chunk)

                def body(carry, v0c):
                    tot, mx = carry
                    # the window fill is dropped: summed over the scan it
                    # loses which windows launched, so no cap x width
                    if labeled:
                        cnt, needed, _ = count_fn(indptr, degrees, flat,
                                                  labs, v0c)
                    else:
                        cnt, needed, _ = count_fn(indptr, degrees, flat,
                                                  v0c)
                    return (tot + cnt, jnp.maximum(mx, needed)), ()

                init = (jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int32))
                (tot, mx), _ = jax.lax.scan(body, init, chunks)
                return jax.lax.psum(tot, axis), jax.lax.pmax(mx, axis)

            self._fns[capacity] = jax.jit(
                jax.shard_map(
                    shard_fn,
                    # P() is a pytree PREFIX for the labs tuple: the label
                    # views are replicated like the CSR arrays
                    mesh=self.mesh,
                    in_specs=(P(), P(), P(), P(), P(axis)),
                    out_specs=(P(), P()),
                    check_vma=False,
                )
            )
        return self._fns[capacity]

    def warmup(self) -> None:
        # all-sentinel frontier: compiles the program without doing the
        # real count (mirrors Matcher.warmup)
        v0 = jnp.full_like(self._v0, self.graph.n)
        with jax.enable_x64(True):
            jax.block_until_ready(
                self._fn(self.cfg.capacity)(*self._call_args(), v0))

    def release(self) -> None:
        """Mirror of :meth:`Matcher.release` — also drops the striped-v0
        device array this matcher privately owns."""
        self._fns.clear()
        self._arrays = None
        self._v0 = None

    def rebind(self, arrays, *, graph=None) -> None:
        """Mirror of :meth:`Matcher.rebind` — the striped v0 layout
        depends only on `n` (unchanged by overlay epochs), so swapping
        the CSR arrays replays the cached shard_map programs as-is."""
        if self._arrays is None:
            raise RuntimeError("matcher was released (evicted from cache)")
        old = jax.tree_util.tree_leaves(tuple(self._arrays))
        new = jax.tree_util.tree_leaves(tuple(arrays))
        if (len(old) != len(new)
                or any(tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype
                       for a, b in zip(old, new))):
            raise ValueError(
                "rebind needs identical array shapes/dtypes; the graph "
                "outgrew its fixed layout — rebuild the matcher")
        if graph is not None:
            if max(graph.max_degree, 1) != self._W:
                raise ValueError(
                    f"rebind window {max(graph.max_degree, 1)} != compiled "
                    f"window {self._W}")
            if graph.n != self.graph.n:
                raise ValueError(
                    f"rebind vertex count {graph.n} != {self.graph.n}")
            self.graph = graph
        self._arrays = arrays

    def count(self) -> CountResult:
        if self._arrays is None:
            raise RuntimeError("matcher was released (evicted from cache)")
        call_args = self._call_args()
        tr = get_tracer()
        # start from the last successful capacity so warm repeats skip
        # the doomed undersized passes, not just their compilation
        capacity = self._capacity
        with tr.span("executor.count", depth=self.plan.depth,
                     sharded=True, chunk=self.chunk) as csp:
            while True:
                with jax.enable_x64(True), tr.span(
                        "executor.dispatch", capacity=capacity,
                        frontier=int(self._v0.shape[0])) as dsp:
                    cnt, needed = self._fn(capacity)(*call_args, self._v0)
                    needed = int(needed)
                    dsp.set(needed=needed)
                if needed <= capacity or capacity >= Matcher.MAX_CAPACITY:
                    break
                while capacity < min(needed, Matcher.MAX_CAPACITY):
                    capacity *= 2
            csp.set(max_needed=needed, capacity=capacity)
        self._capacity = capacity
        return CountResult(
            count=int(cnt) // self.plan.iep_divisor,
            overflowed=needed > capacity,
            max_needed=needed,
        )


def count_embeddings_sharded(
    graph: GraphCSR,
    plan: MatchingPlan,
    mesh,
    *,
    axis: str = "data",
    cfg: ExecutorConfig | None = None,
    chunk: int | None = None,
) -> CountResult:
    """One-shot convenience wrapper around :class:`ShardedMatcher`."""
    return ShardedMatcher(
        graph, plan, mesh, axis=axis, cfg=cfg, chunk=chunk
    ).count()


# --------------------------------------------------------------------------
# graph statistics (bootstraps the performance model with the executor)
# --------------------------------------------------------------------------
def triangle_plan() -> MatchingPlan:
    tri = clique(3)
    rs = generate_restriction_sets(tri, max_sets=1)[0]
    return build_plan(tri, (0, 1, 2), rs)


def compute_stats(
    graph: GraphCSR, cfg: ExecutorConfig | None = None
) -> GraphStats:
    """|V|, |E| and exact triangle count (counted by the system itself)."""
    tri = count_embeddings(graph, triangle_plan(), cfg)
    return GraphStats(
        n_vertices=graph.n, n_edges=graph.m, tri_cnt=tri.count
    )
