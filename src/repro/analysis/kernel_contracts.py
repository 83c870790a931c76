"""Pass 2 — static kernel-contract checking for `level_expand_pallas`.

The fused level-expansion kernel (kernels/intersect.py) states its
safety contract only in docstrings: int32 operands, block-multiple
shapes, a power-of-two gather tile `block_l` so that the aligned tiles
covering every CSR row are rows of the [F/block_l, block_l] view of the
flat array, rows inside the unpadded flat array, scalar-prefetched
offsets that fit in SMEM, and CSR offsets that fit int32.  Violations
surface at trace time, as a Mosaic refusal on the chip, or — for the
offset-overflow case — as wrong reads on device.  This pass proves the
contract abstractly for a given `GraphCSR` shape and `ExecutorConfig`,
mirroring the exact call shapes the executor generates (one spec per
degree bucket, enumeration and IEP-tail variants), and abstractly
evaluates the real `ops.level_expand` wrapper via `jax.eval_shape` +
jaxpr inspection so dtype/shape drift in the wrapper itself is caught
without compiling or running anything.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .findings import ERROR, WARNING, Finding

INT32_MAX = np.iinfo(np.int32).max


def _err(rule: str, loc: str, msg: str) -> Finding:
    return Finding(ERROR, rule, loc, msg)


@dataclass(frozen=True)
class LevelExpandSpec:
    """Static facets of one `ops.level_expand` call site.

    B/D are the pre-padding candidate-matrix shape (the wrapper pads to
    block multiples); `flat_len` is the UNPADDED CSR indices length
    (row extents must end inside it); `padded` says the caller ships a
    `flat_gather_pad()`-sentinel tail that makes whole gather tiles
    (the resident-graph path through `core.executor.device_graph`).
    """

    B: int                    # frontier rows
    D: int                    # candidate columns (window or window+depth)
    P: int                    # predecessor count
    E: int = 0                # extra (restriction/injectivity) columns
    flat_len: int = 0         # unpadded flat CSR length (2m + pad)
    count: bool = False
    neg_from: int | None = None
    padded: bool = True
    block_b: int = 8
    block_l: int = 128
    label: str = "level_expand"


def check_spec(spec: LevelExpandSpec) -> list[Finding]:
    """Contract proofs that need no tracing at all."""
    from ..kernels.intersect import SMEM_PREFETCH_BYTES
    from ..kernels.ops import GATHER_TILE

    loc = spec.label
    out: list[Finding] = []
    bl = spec.block_l
    if bl <= 0 or bl & (bl - 1):
        out.append(_err(
            "kernel-dma-window", loc,
            f"block_l={bl} is not a power of two: the kernel finds a "
            f"row's first gather tile by shifting its CSR offset"))
    elif spec.padded and GATHER_TILE % bl:
        out.append(Finding(
            WARNING, "kernel-dma-window", loc,
            f"block_l={bl} does not divide the resident pad unit "
            f"{GATHER_TILE}: every call re-pads (copies) the flat array "
            f"to whole {bl}-wide tiles"))
    if spec.padded and spec.flat_len == 0:
        out.append(Finding(
            WARNING, "kernel-dma-window", loc,
            "flat_padded=True with an unknown flat length: cannot prove "
            "the row-extent invariant starts + lens <= flat_len"))
    for name, val, mult in (("block_b", spec.block_b, 8),
                            ("block_l", bl, 128)):
        if val <= 0 or val % mult:
            out.append(_err(
                "kernel-block-shape", loc,
                f"{name}={val} is not a positive multiple of {mult} "
                f"(TPU lane/sublane tiling)"))
    if spec.neg_from is not None and not (0 <= spec.neg_from <= spec.D):
        out.append(_err(
            "kernel-window", loc,
            f"neg_from={spec.neg_from} outside candidate columns "
            f"0..{spec.D}: the signed IEP popcount would mis-weight real "
            f"candidates"))
    # SMEM: one call prefetches [P, rows] offsets and lengths; the
    # wrapper splits a frontier into block_b-multiple row groups, so the
    # smallest group must fit
    if 2 * 4 * spec.P * spec.block_b > SMEM_PREFETCH_BYTES:
        out.append(_err(
            "kernel-smem", loc,
            f"P={spec.P} predecessors: even {spec.block_b} rows of "
            f"offsets + lengths exceed the {SMEM_PREFETCH_BYTES}-byte "
            f"scalar-prefetch budget"))

    # int32 reach: the kernel forms tile indices and absolute positions
    # in int32 SMEM scalars; the largest is the end of the last tile,
    # round_up(flat_len, block_l).
    if bl > 0:
        reach = -(-spec.flat_len // bl) * bl
        if reach > INT32_MAX:
            out.append(_err(
                "kernel-int32-offset", loc,
                f"last gather tile ends at {reach} (flat_len="
                f"{spec.flat_len}, tiles of {bl}), past int32: CSR "
                f"offsets wrap and the kernel reads the wrong "
                f"neighborhoods"))
    return out


def abstract_eval_spec(spec: LevelExpandSpec) -> list[Finding]:
    """Trace (never run) the real `ops.level_expand` wrapper with this
    spec's abstract shapes: `jax.eval_shape` catches shape/dtype drift
    between the wrapper and the kernel, and the jaxpr walk proves a
    `pallas_call` with int32 operands is actually on the path."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ops

    loc = spec.label
    out: list[Finding] = []
    pad = ops.flat_gather_pad(spec.flat_len) if spec.padded else 0
    cand = jax.ShapeDtypeStruct((spec.B, spec.D), jnp.int32)
    flat = jax.ShapeDtypeStruct((spec.flat_len + pad,), jnp.int32)
    starts = jax.ShapeDtypeStruct((spec.P, spec.B), jnp.int32)
    lens = jax.ShapeDtypeStruct((spec.P, spec.B), jnp.int32)
    dirs = tuple([0] * spec.E)
    extra = (jax.ShapeDtypeStruct((spec.B, spec.E), jnp.int32)
             if spec.E else None)
    valid = jax.ShapeDtypeStruct((spec.B, spec.D), jnp.bool_)

    def call(cand, flat, starts, lens, extra, valid):
        return ops.level_expand(
            cand, flat, starts, lens, extra, valid,
            dirs=dirs, count=spec.count, neg_from=spec.neg_from,
            block_b=spec.block_b, block_l=spec.block_l, interpret=True,
        )

    try:
        shape = jax.eval_shape(call, cand, flat, starts, lens, extra, valid)
    except Exception as e:          # noqa: BLE001 — any trace rejection
        out.append(_err(
            "kernel-abstract-eval", loc,
            f"abstract evaluation rejects the call: {type(e).__name__}: "
            f"{e}"))
        return out
    want = ((spec.B,), jnp.int32) if spec.count \
        else ((spec.B, spec.D), jnp.bool_)
    if (tuple(shape.shape), shape.dtype) != want:
        out.append(_err(
            "kernel-abstract-eval", loc,
            f"output {shape.shape}/{shape.dtype} drifted from the "
            f"contract {want[0]}/{np.dtype(want[1])}"))

    # jaxpr inspection: a pallas_call must be on the traced path and its
    # integer array operands must all be int32 (dtype drift to int64 —
    # e.g. under x64 — doubles DMA widths and breaks the SMEM prefetch;
    # the wrapper traces the kernel with x64 off).
    try:
        jaxpr = jax.make_jaxpr(call)(cand, flat, starts, lens, extra, valid)
    except Exception:               # eval_shape above already vetted it
        return out
    eqns = list(_iter_eqns(jaxpr.jaxpr))
    pallas = [e for e in eqns if e.primitive.name == "pallas_call"]
    if not pallas:
        out.append(Finding(
            WARNING, "kernel-abstract-eval", loc,
            "no pallas_call primitive in the traced program — the "
            "wrapper silently stopped dispatching the fused kernel"))
    for eqn in pallas:
        for v in eqn.invars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and np.issubdtype(dt, np.integer) \
                    and dt != np.int32:
                out.append(_err(
                    "kernel-dtype-drift", loc,
                    f"pallas_call integer operand has dtype {dt}, "
                    f"contract is int32"))
    return out


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub)


def _sub_jaxprs(eqn):
    from jax._src import core as jcore

    for val in eqn.params.values():
        if isinstance(val, jcore.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jcore.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                if isinstance(v, jcore.ClosedJaxpr):
                    yield v.jaxpr
                elif isinstance(v, jcore.Jaxpr):
                    yield v


def executor_specs(n: int, m: int, max_degree: int, cfg=None,
                   *, label: str = "graph") -> list[LevelExpandSpec]:
    """The call shapes `core.executor.expand_core`/`iep_card_fused`
    actually generate for a graph of this shape under `cfg` — one spec
    per degree bucket × (mask, count, IEP-signed) variant."""
    from ..core.executor import ExecutorConfig

    cfg = cfg or ExecutorConfig()
    W = max(int(max_degree), 1)
    flat_len = 2 * int(m) + W          # CSR build's max-degree pad
    buckets = cfg.degree_buckets
    if buckets is not None:
        buckets = tuple((min(int(w), W), float(f)) for (w, f) in buckets)
        if buckets[-1][0] < W:
            buckets = buckets + ((W, buckets[-1][1]),)
    else:
        buckets = ((W, 1.0),)
    specs = []
    for bi, (width, frac) in enumerate(buckets):
        cap = max(int(cfg.capacity * frac), 8)
        base = dict(P=2, flat_len=flat_len, padded=True)
        specs.append(LevelExpandSpec(
            B=cap, D=width, E=2, count=False,
            label=f"{label}/bucket{bi}[w={width}]/mask", **base))
        specs.append(LevelExpandSpec(
            B=cap, D=width, E=1, count=True,
            label=f"{label}/bucket{bi}[w={width}]/count", **base))
        # IEP tail: prefix vertices ride along as negatively-weighted
        # candidate columns starting at `width`
        specs.append(LevelExpandSpec(
            B=cap, D=width + 4, E=0, count=True, neg_from=width,
            label=f"{label}/bucket{bi}[w={width}]/iep", **base))
    return specs


def check_graph_contract(graph_or_shape, cfg=None, *,
                         deep: bool = False) -> list[Finding]:
    """Prove the kernel contract for a graph shape + executor config.

    `graph_or_shape` is a `GraphCSR` or an (n, m, max_degree) triple —
    the latter lets CI reason about graphs too big to materialize.
    `deep=True` additionally traces every generated call site
    abstractly (eval_shape + jaxpr walk); the shape proofs alone are
    pure arithmetic.
    """
    if hasattr(graph_or_shape, "indptr"):
        n, m = graph_or_shape.n, graph_or_shape.m
        W = graph_or_shape.max_degree
        label = graph_or_shape.name or "graph"
    else:
        n, m, W = graph_or_shape
        label = f"shape(n={n},m={m},W={W})"
    out: list[Finding] = []
    from ..kernels.ops import flat_gather_pad

    flat_len = 2 * m + W
    padded = flat_len + flat_gather_pad(flat_len)
    if padded > INT32_MAX:
        out.append(_err(
            "kernel-int32-offset", label,
            f"padded flat CSR length {padded} overflows int32 indexing; "
            f"the graph needs int64 offsets the kernel does not "
            f"implement"))
    if n > INT32_MAX:
        out.append(_err(
            "kernel-int32-offset", label,
            f"|V|={n} overflows int32 vertex ids"))
    for spec in executor_specs(n, m, W, cfg, label=label):
        out += check_spec(spec)
        if deep and not out:
            out += abstract_eval_spec(spec)
    return out
