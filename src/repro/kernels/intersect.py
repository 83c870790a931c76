"""Pallas TPU kernels for the set-intersection hot spot.

GraphPi's inner loop is a sorted-list merge intersection — a pointer-
chasing pattern that does not vectorize on TPU.  The TPU-native
formulation (DESIGN.md §3) is *blocked broadcast-compare*: tile the
candidate row and the neighbor row into VREG-shaped blocks in VMEM and
reduce equality matches across the neighbor dimension.  Arithmetic
intensity is D·L compares per D+L loaded words, so for typical
neighborhood lengths the kernel is compute-dense on the VPU instead of
latency-bound like a merge.

Three kernels:
  membership_kernel      mask[b, d] = cand[b, d] ∈ nbr[b, :]
  intersect_count_kernel cnt[b]     = |{d : cand[b, d] ∈ nbr[b, :]}|
                         (membership + in-kernel popcount, fused)
  level_expand_kernel    the executor's whole per-level admissibility
                         test in ONE pass: membership against ALL
                         predecessor neighborhoods, the asymmetric-
                         restriction comparisons and injectivity !=
                         masks against per-row prefix vertices, reduced
                         to either a mask or an in-kernel popcount.

`level_expand_kernel` is self-feeding (DESIGN.md §4): it never sees a
materialized `[P, B, W]` stack of predecessor windows.  The CSR row
offsets and lengths of every predecessor arrive as scalar-prefetch
operands (`PrefetchScalarGridSpec`, resident in SMEM before the body
runs) and each neighborhood is DMA'd, in the aligned 128-entry tiles
that cover it, out of the flat CSR `indices` array — which stays
unblocked in HBM (`memory_space=ANY`) — into a VMEM scratch buffer
inside the grid.  A block of rows walks only as many tiles as its
longest row needs, so power-law short rows cost one or two tiles
whatever the max degree.  The `count=True` path optionally applies a signed
weight per candidate column (`neg_from`): columns ≥ `neg_from` subtract
instead of add, which lets the executor fold the IEP prefix-correction
cardinalities into the same pass (the prefix vertices ride along as
negatively-weighted candidates).

Padding contract: `cand` padded with -1, neighbor entries outside a
row's extent masked to INT_MAX in-kernel (rows sorted ascending), so
padding never produces a match.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NBR_PAD = jnp.iinfo(jnp.int32).max
CAND_PAD = -1


def _membership_body(cand_ref, nbr_ref, out_ref, *, block_l: int):
    """Grid = (B/bb, D/bd, L/bl); L is the innermost (accumulation) dim."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    cand = cand_ref[...]                  # [bb, bd]
    nbr = nbr_ref[...]                    # [bb, bl]
    # broadcast-compare: [bb, bd, bl] equality cube, reduced over bl
    hit = (cand[:, :, None] == nbr[:, None, :]).any(axis=-1)
    out_ref[...] |= hit


def _count_body(cand_ref, nbr_ref, out_ref, acc_ref, *, block_l: int):
    """Fused |A ∩ B| per row: the [bb, bd, bl] equality cube is reduced
    over BOTH d and l inside the kernel; the row accumulator lives in VMEM
    scratch and is flushed once per row-block.

    Contract: nbr rows strictly increasing on their valid prefix (CSR
    neighborhoods are), so a candidate matches in at most one l-block.
    """
    j = pl.program_id(1)
    k = pl.program_id(2)
    nj = pl.num_programs(1)
    nk = pl.num_programs(2)

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cand = cand_ref[...]                  # [bb, bd]
    nbr = nbr_ref[...]                    # [bb, bl]
    hit = (cand[:, :, None] == nbr[:, None, :]).any(axis=-1)
    acc_ref[...] += hit.sum(axis=1, keepdims=True).astype(jnp.int32)

    @pl.when((j == nj - 1) & (k == nk - 1))
    def _flush():
        out_ref[...] = acc_ref[...]


def _tile_hits(cand, nbr):
    """hit[r, d] = cand[r, d] ∈ nbr[r, :] as int32, for one [bb, bl]
    candidate chunk against one [bb, bl] neighbor tile: every lane
    rotation of the tile is compared with the candidates, so each
    candidate meets every neighbor of its own row once — bl vreg
    rotations, no cross-lane reduction."""
    hit = cand == nbr
    for shift in range(1, nbr.shape[1]):
        hit |= cand == pltpu.roll(nbr, shift, 1)
    return hit.astype(jnp.int32)


def _level_expand_body(starts_sref, lens_sref, cand_ref, flat_ref, *refs,
                       n_preds: int, dirs: tuple, count: bool,
                       neg_from: int | None, block_b: int, block_l: int):
    """Fused, self-feeding per-level admissibility test.

    Grid = (B/bb, P): step (i, p) tests the bb×D candidate block of
    frontier rows i·bb.. against predecessor p's neighborhoods.  Each
    neighborhood flat[s : s+len] is walked in the ALIGNED `block_l`-wide
    tiles that cover it — tile t of row r is row `s//block_l + t` of the
    [F/block_l, block_l] view of the flat CSR array — so every DMA
    starts on a tile boundary whatever the row's CSR offset.  Entries
    outside [s, s+len) are masked to NBR_PAD by their absolute position;
    the membership test needs no lane shift.  The tile loop runs only as
    far as this block's longest row, so rows of a short-degree block
    cost one or two tiles, not the window's max degree.

    A VMEM hit-accumulator counts, for each candidate, in how many
    predecessor neighborhoods it was found (CSR rows are STRICTLY
    increasing, so each row matches a candidate at most once, even
    across tiles); admissibility is hits == P, ANDed with the
    restriction (>/<) and injectivity (!=) comparisons against the
    per-row prefix-vertex values in `extra`, applied after the last
    predecessor.

    refs layout:
      starts [P, B'] int32 SMEM (scalar prefetch) — CSR row offsets
      lens   [P, B'] int32 SMEM (scalar prefetch) — row lengths
      cand   [bb, D]          candidate block (CAND_PAD-masked)
      flat   [F/bl, bl]       whole CSR indices array, unblocked (HBM/ANY)
      extra  [bb, E]          prefix-vertex values, E == len(dirs) (if E)
      out    [bb, D] bool mask — or [bb, 1] int32 row counts if `count`
      nbr    [bb, bl] int32 VMEM scratch (DMA landing buffer)
      hits   [bb, D]  int32 VMEM scratch
      sems   [bb] DMA semaphores (one per frontier row)
    """
    if dirs:
        extra_ref, out_ref, nbr_ref, hits_ref, sems_ref = refs
    else:
        out_ref, nbr_ref, hits_ref, sems_ref = refs
        extra_ref = None
    i = pl.program_id(0)
    p = pl.program_id(1)
    shift = block_l.bit_length() - 1          # block_l is a power of two
    n_dblocks = cand_ref.shape[1] // block_l

    @pl.when(p == 0)
    def _init():
        hits_ref[...] = jnp.zeros_like(hits_ref)

    # per-row extent [s, e) and first tile; rows of length 0 walk nothing
    s = [starts_sref[p, i * block_b + r] for r in range(block_b)]
    e = [s[r] + lens_sref[p, i * block_b + r] for r in range(block_b)]
    t0 = [jnp.right_shift(s[r], shift) for r in range(block_b)]
    nt = [jnp.where(e[r] > s[r],
                    jnp.right_shift(e[r] - 1, shift) - t0[r] + 1, 0)
          for r in range(block_b)]
    n_tiles = functools.reduce(jnp.maximum, nt)
    sub = jax.lax.broadcasted_iota(jnp.int32, (block_b, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_b, block_l), 1)

    def tile(t, carry):
        dmas = []
        for r in range(block_b):
            dma = pltpu.make_async_copy(
                flat_ref.at[t0[r] + t], nbr_ref.at[r], sems_ref.at[r])
            live = t < nt[r]

            @pl.when(live)
            def _start(dma=dma):
                dma.start()

            dmas.append((live, dma))
        for live, dma in dmas:
            @pl.when(live)
            def _wait(dma=dma):
                dma.wait()

        # keep only entries whose absolute position lies in [s, e); a
        # row whose tiles ran out (or was never fetched) keeps none
        lo = jnp.zeros((block_b, 1), jnp.int32)
        hi = jnp.zeros((block_b, 1), jnp.int32)
        for r in range(block_b):
            base = jnp.left_shift(t0[r] + t, shift)
            lo = jnp.where(sub == r, s[r] - base, lo)
            hi = jnp.where(sub == r, e[r] - base, hi)
        nbr = jnp.where((lane >= lo) & (lane < hi), nbr_ref[...], NBR_PAD)

        def dblock(jb, c):
            off = pl.multiple_of(jb * block_l, block_l)
            cand = cand_ref[:, pl.ds(off, block_l)]          # [bb, bl]
            hits_ref[:, pl.ds(off, block_l)] += _tile_hits(cand, nbr)
            return c

        return jax.lax.fori_loop(0, n_dblocks, dblock, carry)

    jax.lax.fori_loop(0, n_tiles, tile, 0)

    @pl.when(p == n_preds - 1)
    def _finalize():
        cand = cand_ref[...]
        mask = hits_ref[...] == n_preds
        for k, d in enumerate(dirs):
            ev = extra_ref[:, k][:, None]  # [bb, 1]
            if d > 0:
                mask &= cand > ev
            elif d < 0:
                mask &= cand < ev
            else:
                mask &= cand != ev
        if not count:
            out_ref[...] = mask
            return
        w = mask.astype(jnp.int32)
        if neg_from is not None:
            # signed popcount: columns ≥ neg_from are the IEP
            # prefix-correction candidates and subtract instead of add
            col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
            w = jnp.where(col < neg_from, w, -w)
        out_ref[...] = w.sum(axis=1, keepdims=True)


# Bytes of row offsets + lengths one pallas_call may scalar-prefetch.
# SMEM is 1 MiB on v5e and also holds the kernel's own scalars, so the
# wrapper splits a frontier into row groups of at most
# SMEM_PREFETCH_BYTES / (2·P·4) rows, one pallas_call each.
SMEM_PREFETCH_BYTES = 256 << 10


def prefetch_rows(n_preds: int, block_b: int) -> int:
    """Frontier rows per pallas_call: the largest block_b multiple whose
    [P, rows] starts + lens fit in SMEM_PREFETCH_BYTES."""
    rows = SMEM_PREFETCH_BYTES // (2 * 4 * max(n_preds, 1))
    return max(block_b, rows // block_b * block_b)


def level_expand_pallas(
    cand: jax.Array,                      # [B, D] int32, CAND_PAD-masked
    flat: jax.Array,                      # [F/block_l, block_l] int32
    starts: jax.Array,                    # [P, B] int32 CSR row offsets
    lens: jax.Array,                      # [P, B] int32 row lengths
    extra: jax.Array | None = None,       # [B, E] int32 (E == len(dirs))
    *,
    dirs: tuple = (),
    count: bool = False,
    neg_from: int | None = None,
    block_b: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """One fused, self-feeding pass per expansion level (cand pre-padded
    to block multiples — ops.level_expand handles that).

    mask[b, d] = (∀p: cand[b, d] ∈ flat[starts[p,b] : +lens[p,b]])
               ∧ extras(b, d), where extras applies dirs[e] ∈
    {+1: cand > extra[b, e], -1: cand <, 0: cand !=}.  `count=True`
    instead returns cnt[b] = Σ_d mask[b, d] via the in-kernel popcount;
    with `neg_from` set, columns ≥ neg_from are weighted −1 (the fused
    IEP prefix-correction tail — DESIGN.md §4).

    `flat` is the flat CSR array viewed as tiles of block_l entries
    (block_l = flat.shape[1], a power of two); candidates are compared
    in chunks of the same width, so D is a multiple of block_l.  DMA safety contract:
    every row lies inside the array (starts + lens ≤ F), so the tiles
    that cover it are rows of `flat`; nothing else is read.

    The kernel is traced with x64 off: every operand and index is int32
    whatever the caller's mode, and Mosaic never sees a 64-bit type.
    Frontiers longer than `prefetch_rows(P, block_b)` run as several
    calls so the scalar-prefetched offsets fit in SMEM.
    """
    B, D = cand.shape
    P, Bs = starts.shape
    block_l = flat.shape[1]
    assert B == Bs and P >= 1, (cand.shape, starts.shape)
    assert lens.shape == (P, B), (lens.shape, starts.shape)
    assert B % block_b == 0 and D % block_l == 0, (cand.shape, block_b,
                                                   block_l)
    assert block_l & (block_l - 1) == 0, block_l
    assert neg_from is None or count
    body = functools.partial(
        _level_expand_body, n_preds=P, dirs=tuple(dirs), count=count,
        neg_from=neg_from, block_b=block_b, block_l=block_l)
    rows = prefetch_rows(P, block_b)
    outs = []
    with jax.enable_x64(False):
        for r0 in range(0, B, rows):
            nb = min(rows, B - r0)
            in_specs = [
                pl.BlockSpec((block_b, D), lambda i, p, ss, ls: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ]
            operands = [cand[r0:r0 + nb], flat]
            if dirs:
                assert extra is not None and extra.shape == (B, len(dirs))
                in_specs.append(pl.BlockSpec(
                    (block_b, len(dirs)), lambda i, p, ss, ls: (i, 0)))
                operands.append(extra[r0:r0 + nb])
            if count:
                out_specs = pl.BlockSpec((block_b, 1),
                                         lambda i, p, ss, ls: (i, 0))
                out_shape = jax.ShapeDtypeStruct((nb, 1), jnp.int32)
            else:
                out_specs = pl.BlockSpec((block_b, D),
                                         lambda i, p, ss, ls: (i, 0))
                out_shape = jax.ShapeDtypeStruct((nb, D), jnp.bool_)
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(nb // block_b, P),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[
                    pltpu.VMEM((block_b, block_l), jnp.int32),
                    pltpu.VMEM((block_b, D), jnp.int32),
                    pltpu.SemaphoreType.DMA((block_b,)),
                ],
            )
            outs.append(pl.pallas_call(
                body, grid_spec=grid_spec, out_shape=out_shape,
                interpret=interpret, name="level_expand",
            )(starts[:, r0:r0 + nb], lens[:, r0:r0 + nb], *operands))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out[:, 0] if count else out


def membership_pallas(
    cand: jax.Array,
    nbr: jax.Array,
    *,
    block_b: int = 8,
    block_d: int = 128,
    block_l: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """mask[b, d] = cand[b, d] ∈ nbr[b, :].  Shapes must be pre-padded to
    block multiples (ops.sorted_membership handles that)."""
    B, D = cand.shape
    Bn, L = nbr.shape
    assert B == Bn, (cand.shape, nbr.shape)
    assert B % block_b == 0 and D % block_d == 0 and L % block_l == 0
    grid = (B // block_b, D // block_d, L // block_l)
    return pl.pallas_call(
        functools.partial(_membership_body, block_l=block_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_l), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.bool_),
        interpret=interpret,
    )(cand, nbr)


def intersect_count_pallas(
    cand: jax.Array,
    nbr: jax.Array,
    *,
    block_b: int = 8,
    block_d: int = 128,
    block_l: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """cnt[b] = |{d : cand[b, d] ∈ nbr[b, :]}| (int32), fully fused: the
    d and l reductions happen in-kernel, output is one scalar per row."""
    B, D = cand.shape
    _, L = nbr.shape
    assert B % block_b == 0 and D % block_d == 0 and L % block_l == 0
    grid = (B // block_b, D // block_d, L // block_l)
    out = pl.pallas_call(
        functools.partial(_count_body, block_l=block_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_l), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, 1), jnp.int32)],
        interpret=interpret,
    )(cand, nbr)
    return out[:, 0]
